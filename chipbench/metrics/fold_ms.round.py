"""Host time per round of the broker's fold at the seal (``ingest.fold``):
each admitted client's decoded parameters pulled back from the device and
raced into the reservoir.  The program's own span, summed over the window
÷ rounds."""
from yardstick import program_spans as PS


def read(run):
    return PS.per_step_ms(run, "ingest.fold")
