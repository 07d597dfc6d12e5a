"""Host time per round of staging the round program's inputs
(``round.stage``): the reservoir's stack padded to the compiled signature
and sent to the device.  The program's own span, summed over the window
÷ rounds."""
from yardstick import program_spans as PS


def read(run):
    return PS.per_step_ms(run, "round.stage")
