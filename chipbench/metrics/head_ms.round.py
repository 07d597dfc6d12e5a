"""Time per round of the round program (``round.program``): the compiled
executable called and the new head waited for on the device.  The
program's own span, summed over the window ÷ rounds."""
from yardstick import program_spans as PS


def read(run):
    return PS.per_step_ms(run, "round.program")
