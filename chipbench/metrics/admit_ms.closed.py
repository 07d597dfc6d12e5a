"""Mean host time of a serving step's admission (``service.admit``):
requests pulled into slots and the host batch of tokens and lengths
built.  The program's own span, averaged over the window's steps."""
from yardstick import program_spans as PS


def read(run):
    return PS.mean_ms(run, "service.admit")
