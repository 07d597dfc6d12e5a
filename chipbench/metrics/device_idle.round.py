"""Share of the traced window in which no operation ran on the device:
1 − (union of the device's operation intervals) ÷ window."""
from yardstick import tracing as TR


def read(run):
    idle = TR.idle_share(run.trace) if run.trace is not None else None
    return None if idle is None else 100.0 * idle
