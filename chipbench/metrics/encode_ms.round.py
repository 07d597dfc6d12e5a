"""Mean host time of one client's wire work (``wire.encode``): codec
encode, decode of its own payload, and the decoded parameters uploaded.
The program's own span, averaged over the window's clients."""
from yardstick import program_spans as PS


def read(run):
    return PS.mean_ms(run, "wire.encode")
