"""The profiler trace, read into plain events, and its reduction to metrics.

``capture`` records one window with JAX's profiler; ``load`` turns the
``.xplane.pb`` it writes into a small ``Trace``: the device's operations
(the "XLA Ops" line of each ``/device:TPU:<n>`` plane, each named by its
HLO instruction, e.g. ``%estep_fused.7``) and the host spans the
benchmark's own files opened with ``span`` (names starting ``cb.``), all
on the profiler's one clock.  An operation such as a ``while`` loop spans
the operations of its body, so the line nests.  Every reduction below
works on that ``Trace``, so a recorded one checks them without a chip.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "cb."
WINDOW = "cb.window"
OPS_LINE = "XLA Ops"

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)


def span(name: str):
    """A host span in the profiler's trace (inert when nothing traces)."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


@contextlib.contextmanager
def capture(directory: str):
    import jax
    jax.profiler.start_trace(directory)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Trace:
    """Device operations per chip and the benchmark's host spans."""
    ops: Dict[str, List[Event]]
    spans: List[Event]

    def window(self) -> Tuple[float, float]:
        wins = [(s, e) for n, s, e in self.spans if n == WINDOW]
        if not wins:
            raise ValueError("the trace holds no cb.window span")
        return wins[-1]

    def to_json(self) -> str:
        return json.dumps({"ops": self.ops, "spans": self.spans})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        raw = json.loads(text)
        return cls({k: [tuple(e) for e in v] for k, v in raw["ops"].items()},
                   [tuple(e) for e in raw["spans"]])


def load(directory: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops.setdefault(plane.name, []).extend(
                            (op_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns)
                            for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    spans.extend((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                                 for e in line.events
                                 if e.name.startswith(SPAN_PREFIX))
    return Trace(ops, spans)


def op_name(text: str) -> str:
    """The HLO instruction's name: what precedes `` = `` in the op's text
    (the rest lists shapes and operands, which name other ops)."""
    return text.split(" = ", 1)[0]


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged busy intervals, in order."""
    out: List[List[float]] = []
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace) -> Optional[float]:
    """Seconds of the window in which an operation ran, averaged over the
    chips; None where the trace holds no device operation."""
    if not trace.ops:
        return None
    lo, hi = trace.window()
    per_chip = [sum(e - s for s, e in union(clip(evs, lo, hi)))
                for evs in trace.ops.values()]
    return sum(per_chip) / len(per_chip) * 1e-9


def window_s(trace: Trace) -> float:
    lo, hi = trace.window()
    return (hi - lo) * 1e-9


def idle_share(trace: Trace) -> Optional[float]:
    busy = busy_s(trace)
    return None if busy is None else 1.0 - busy / window_s(trace)


def kernel_s(trace: Trace, fragment: str) -> Optional[float]:
    """Summed device time of the operations whose instruction name holds
    ``fragment``, averaged over chips; None where there is none."""
    lo, hi = trace.window()
    per_chip = [sum(e - s for n, s, e in clip(evs, lo, hi) if fragment in n)
                for evs in trace.ops.values()]
    total = sum(per_chip)
    return total / len(per_chip) * 1e-9 if total > 0 else None


def self_times(events: Sequence[Event]) -> List[Tuple[str, float]]:
    """Each operation's time less the time of the operations nested in it
    (a loop's own time, not its body's), in ns."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    own = [ev[2] - ev[1] for ev in order]
    stack: List[int] = []
    for i, (_, s, e) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(order[i][0], own[i]) for i in range(len(order))]


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The device operations that took most time of their own, summed by
    name and averaged over chips."""
    lo, hi = trace.window()
    by: Dict[str, float] = {}
    for evs in trace.ops.values():
        for name, t in self_times(clip(evs, lo, hi)):
            by[name] = by.get(name, 0.0) + t * 1e-9 / len(trace.ops)
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def host_span_at(spans: Sequence[Event], t: float) -> str:
    """The innermost benchmark span open at ``t`` (the shortest that
    covers it), other than the window itself."""
    open_ = [(e - s, name) for name, s, e in spans
             if s <= t <= e and name != WINDOW]
    if not open_:
        return "no span"
    return min(open_)[1][len(SPAN_PREFIX):]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The longest idle gaps of the first chip, each named by the host
    span open at its middle."""
    if not trace.ops:
        return []
    lo, hi = trace.window()
    busy = union(clip(next(iter(trace.ops.values())), lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i + 1] - edges[i], edges[i])
            for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    gaps.sort(reverse=True)
    return [[host_span_at(trace.spans, s + g / 2), g * 1e-9]
            for g, s in gaps[:n]]

