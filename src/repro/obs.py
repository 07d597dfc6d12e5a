"""Spans: the program's one tracing system.

``span(name, **ids)`` times one phase of the program.  It does two things:

* it opens ``jax.profiler.TraceAnnotation(name)``, so the phase lands in
  the profiler's host plane beside the device's operations whenever a
  profiler session is running (and costs next to nothing when none is);
* on exit it appends a :class:`Record` to a process-wide ring of
  :data:`CAPACITY` records, timed on ``time.perf_counter_ns`` — the clock
  of ``ServiceRequest``'s timestamps.

The ring is always on: it is the operator's flight recorder, holding what
the process did in its last few thousand phases, profiler or not.  Spans
nest per thread; a span's ``parent`` is the ``seq`` of the span that
encloses it, and it inherits that span's ids (``step=``, ``round=``,
``client=``), so every phase of one request or round carries its id.

``spans()`` and ``reset()`` are the reads.  Counts of work done live with
the object that does it (``FedPFTService.stats()``,
``IngestBroker.accounting()``, a round's ``info``), not here.

The spans of the service path, outermost first::

    service.step (step=)      one serving step
      service.admit           slot admission and the host batch build
      service.dispatch        the feature step (and head logits) dispatched
      service.readback        device_get of the step's results
    client.fit (client=)      the client's classwise EM, dispatched
    client.readback           device_get of the fitted parameters
    wire.encode               codec encode, decode, re-upload
    ingest.submit (client=)   the broker's admission of one message
    service.close_round (round=)
      ingest.fold             decoded parameters pulled back and folded
      round.stage             the reservoir's stack padded and uploaded
      round.program           the round program run to a ready head
      service.install         head swap, fresh broker, deferred re-queue
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Deque, Dict, List, NamedTuple, Optional

import jax

__all__ = ["CAPACITY", "Record", "span", "spans", "reset"]

# records the ring keeps: at the service's ~85 spans per federation round
# and 4 per serving step, the last several hundred rounds or ~16k steps
CAPACITY = 1 << 16


class Record(NamedTuple):
    """One closed span.  ``parent`` is the enclosing span's ``seq``, or
    None at the top of its thread's stack."""
    name: str
    t0_ns: int
    t1_ns: int
    parent: Optional[int]
    ids: Dict[str, object]
    seq: int


_ring: Deque[Record] = collections.deque(maxlen=CAPACITY)
_seq = itertools.count()
_now = time.perf_counter_ns
_annotation = jax.profiler.TraceAnnotation


class _Stacks(threading.local):
    def __init__(self):
        self.stack: List["_Open"] = []


_local = _Stacks()


class _Open:
    """An open span; after the ``with`` block, ``elapsed_ns`` is its
    duration."""

    __slots__ = ("name", "ids", "parent", "seq", "t0_ns", "t1_ns", "_ann",
                 "_stack")

    def __init__(self, name: str, ids: Dict[str, object]):
        self.name, self.ids = name, ids

    def __enter__(self) -> "_Open":
        stack = self._stack = _local.stack
        if stack:
            top = stack[-1]
            self.parent = top.seq
            self.ids = {**top.ids, **self.ids} if self.ids else top.ids
        else:
            self.parent = None
        self.seq = next(_seq)
        stack.append(self)
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self.t0_ns = _now()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1_ns = _now()
        self._ann.__exit__(*exc)
        self._stack.pop()
        _ring.append(Record(self.name, self.t0_ns, self.t1_ns, self.parent,
                            self.ids, self.seq))
        return False

    @property
    def elapsed_ns(self) -> int:
        return self.t1_ns - self.t0_ns


def span(name: str, **ids) -> _Open:
    """A context manager timing one phase: ``with span("ingest.fold"):``."""
    return _Open(name, ids)


def spans(since_ns: Optional[int] = None) -> List[Record]:
    """The ring's records in the order they closed; with ``since_ns``,
    those that opened at or after it."""
    recs = list(_ring)
    if since_ns is None:
        return recs
    return [r for r in recs if r.t0_ns >= since_ns]


def reset() -> None:
    """Empty the ring."""
    _ring.clear()
