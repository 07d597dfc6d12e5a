"""The program's own spans, read for the window of a traced run.

The service records each phase of its path in an in-process ring
(``repro.obs``) on ``time.perf_counter_ns``, the clock of the step log
(``harness.StepLog``); so the window runs from the first step's start to
the last step's end on that clock, and a span belongs to it when it lies
inside.
These readers read the ring after the window.  Each gives ``None`` where
the program has no recorder (a program from before it), where no program
span lies in the window, where the ring filled and dropped spans that
closed after the window opened (a window longer than the ring holds), or
where the run's trace holds no device operation (a run off the chip), as
the trace's own metrics do.

``trace_offset`` places program spans on the profiler's clock.  The round
cell reads each step's start on ``perf_counter`` just before it opens
``cb.round`` and the step's end just after it closes it, so on the
trace's clock every ``cb.round`` lies inside its step: the offset is at
most min(span start − step start) and at least max(span end − step end).
The map takes the middle of those bounds, and is refused where they lie
more than ``MAX_SPREAD_NS`` apart, either way: a drift between the clocks,
or spans and steps that do not pair.  (The span sometimes opens up to
a quarter of a millisecond after the step's first reading on a TPU v5e
host, and never late at its close, so the start alone would not pin the
offset.)
"""
from __future__ import annotations

from typing import List, Optional, Sequence

from yardstick import tracing as TR

MAX_SPREAD_NS = 50_000


def window_ns(rows: Sequence) -> tuple:
    """(first step's start, last step's end) on ``perf_counter_ns``."""
    return round(rows[0][0] * 1e9), round(rows[-1][1] * 1e9)


def in_window(run) -> Optional[List]:
    """The program's spans inside the run's window, or None."""
    steps = run.record.get("step_log")
    if run.trace is None or not run.trace.ops or steps is None \
            or not steps.rows:
        return None
    try:
        from repro import obs
    except ImportError:
        return None
    lo, hi = window_ns(steps.rows)
    recs = obs.spans()
    if len(recs) >= getattr(obs, "CAPACITY", float("inf")) \
            and recs[0].t1_ns > lo:
        return None     # the full ring has dropped spans of the window
    got = [s for s in recs if lo <= s.t0_ns and s.t1_ns <= hi]
    return got or None


def durations_ms(spans: Sequence, name: str) -> List[float]:
    return [(s.t1_ns - s.t0_ns) * 1e-6 for s in spans if s.name == name]


def per_step_ms(run, name: str) -> Optional[float]:
    """Summed time of the spans ``name`` in the window ÷ the steps (a
    round is one step of the round cell's log)."""
    spans = in_window(run)
    got = durations_ms(spans, name) if spans else []
    return sum(got) / len(run.record["step_log"].rows) if got else None


def mean_ms(run, name: str) -> Optional[float]:
    """Mean time of one span ``name`` in the window."""
    spans = in_window(run)
    got = durations_ms(spans, name) if spans else []
    return sum(got) / len(got) if got else None


def trace_offset(trace: TR.Trace, rows: Sequence,
                 anchor: str) -> Optional[float]:
    """ns to add to a ``perf_counter_ns`` reading to place it on the
    trace's clock, from the ``cb.<anchor>`` span each step opens first and
    closes last; None where spans and steps do not pair one to one or the
    two bounds lie more than ``MAX_SPREAD_NS`` apart."""
    spans = sorted((s, e) for n, s, e in trace.spans
                   if n == TR.SPAN_PREFIX + anchor)
    if not rows or len(spans) != len(rows):
        return None
    hi = min(s - round(r[0] * 1e9) for (s, _), r in zip(spans, rows))
    lo = max(e - round(r[1] * 1e9) for (_, e), r in zip(spans, rows))
    if abs(hi - lo) > MAX_SPREAD_NS:
        return None
    return (lo + hi) / 2


def overlap_ns(a: Sequence, b: Sequence) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_inside_share(trace: TR.Trace, spans: Sequence[TR.Event]
                      ) -> Optional[float]:
    """Share of the traced window's device-idle time that lies inside the
    union of ``spans`` (already on the trace's clock), over all chips."""
    lo, hi = trace.window()
    cover = TR.union(TR.clip(spans, lo, hi))
    idle = inside = 0.0
    for evs in trace.ops.values():
        busy = TR.union(TR.clip(evs, lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        idle += sum(e - s for s, e in gaps)
        inside += overlap_ns(gaps, cover)
    return inside / idle if idle > 0 else None
