"""Share of the traced window's device-idle time that lies inside the
seal's host work: the program's ``ingest.fold`` and ``round.stage`` spans,
placed on the trace's clock through the ``cb.round`` span each round
opens."""
from yardstick import program_spans as PS

SEAL = ("ingest.fold", "round.stage")


def read(run):
    spans = PS.in_window(run)
    if spans is None:
        return None
    off = PS.trace_offset(run.trace, run.record["step_log"].rows, "round")
    if off is None:
        return None
    seal = [(s.name, s.t0_ns + off, s.t1_ns + off) for s in spans
            if s.name in SEAL]
    share = PS.idle_inside_share(run.trace, seal) if seal else None
    return None if share is None else 100.0 * share
