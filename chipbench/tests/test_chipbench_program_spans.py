"""The readers of the program's own spans (``repro.obs``) on the CPU: each
reads a recording made on one v5e chip, the clock map places the program's
spans on the trace's clock and refuses a map that spreads, and every
reader gives None where the program recorded nothing, where it has no
recorder, or where the trace holds no device operation."""
from __future__ import annotations

import json
import pathlib
import sys
import types

import pytest

import cb_tiny
from yardstick import harness as HN
from yardstick import program_spans as PS
from yardstick import tracing as TR

DATA = pathlib.Path(__file__).resolve().parent / "data"
REC = json.loads((DATA / "recorded_program_spans.json").read_text())

# metric → (recording, how the reader reduces its span, span name)
READERS = {"fold_ms.round": ("round", "per_step", "ingest.fold"),
           "stage_ms.round": ("round", "per_step", "round.stage"),
           "head_ms.round": ("round", "per_step", "round.program"),
           "encode_ms.round": ("round", "mean", "wire.encode"),
           "seal_idle.round": ("round", "seal", None),
           "admit_ms.closed": ("extract", "mean", "service.admit")}


def read(metric):
    return HN.reader(types.SimpleNamespace(root=cb_tiny.REPO), metric)


def recorded_run(which: str, ops: bool = True) -> HN.Run:
    r = REC[which]
    trace = TR.Trace.from_json(json.dumps(r["trace"]))
    if not ops:
        trace.ops = {}
    steps = HN.StepLog()
    steps.rows = [tuple(row) for row in r["rows"]]
    return HN.Run(cell=None, trace=trace, record={"step_log": steps},
                  peaks={})


@pytest.fixture
def ring(monkeypatch):
    """Hands the readers a recording's program spans as the ring."""
    from repro import obs

    def use(records):
        recs = [obs.Record(*s) for s in records]
        monkeypatch.setattr(obs, "spans", lambda since_ns=None: list(recs))
    return use


def swept_seal_share(run: HN.Run) -> float:
    """seal_idle's number by one sweep over every edge: the window's time
    with no operation open and a fold or stage span open, ÷ its time with
    no operation open; the spans placed by the middle of the offsets that
    keep each ``cb.round`` inside its step."""
    rows = run.record["step_log"].rows
    anchors = sorted((s, e) for n, s, e in run.trace.spans
                     if n == "cb.round")
    hi = min(s - round(r[0] * 1e9) for (s, _), r in zip(anchors, rows))
    lo = max(e - round(r[1] * 1e9) for (_, e), r in zip(anchors, rows))
    off = (lo + hi) / 2
    w0, w1 = run.trace.window()
    edges = [(t, kind, d) for evs in run.trace.ops.values()
             for _, s, e in evs for t, kind, d in ((s, 0, 1), (e, 0, -1))]
    edges += [(t, 1, d) for sp in REC["round"]["program_spans"]
              if sp[0] in ("ingest.fold", "round.stage")
              for t, d in ((sp[1] + off, 1), (sp[2] + off, -1))]
    open_ = [0, 0]
    idle = inside = 0.0
    last = w0
    for t, kind, d in sorted(edges):
        t = min(max(t, w0), w1)
        if open_[0] == 0:
            idle += t - last
            inside += (t - last) if open_[1] else 0.0
        open_[kind] += d
        last = t
    idle += w1 - last if open_[0] == 0 else 0.0
    inside += w1 - last if open_[0] == 0 and open_[1] else 0.0
    return 100.0 * inside / idle


@pytest.mark.parametrize("metric", sorted(READERS))
def test_each_reader_reads_the_recording(ring, metric):
    which, how, name = READERS[metric]
    rec = REC[which]
    ring(rec["program_spans"])
    run = recorded_run(which)
    got = read(metric)(run)
    if how == "seal":
        assert got == pytest.approx(swept_seal_share(run), rel=1e-9)
        assert 0 < got < 100
        return
    ms = [(s[2] - s[1]) * 1e-6 for s in rec["program_spans"] if s[0] == name]
    assert ms, name
    want = sum(ms) / (len(rec["rows"]) if how == "per_step" else len(ms))
    assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_reader_reads_nothing_without_program_spans(ring, metric):
    ring([])
    assert read(metric)(recorded_run(READERS[metric][0])) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_reader_reads_nothing_from_a_program_without_a_recorder(
        monkeypatch, metric):
    """Against a program that has no ``repro.obs`` (the import fails),
    each reader gives None and does not raise."""
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read(metric)(recorded_run(READERS[metric][0])) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_reader_reads_nothing_off_the_chip(ring, metric):
    which = READERS[metric][0]
    ring(REC[which]["program_spans"])
    assert read(metric)(recorded_run(which, ops=False)) is None


def test_spans_outside_the_window_are_left_out(ring):
    rec = REC["round"]
    late = rec["rows"][-1][1] * 1e9 + 1e6
    extra = ["ingest.fold", round(late), round(late + 5e8), None, {}, -1]
    ring(rec["program_spans"] + [extra])
    base = sum((s[2] - s[1]) * 1e-6 for s in rec["program_spans"]
               if s[0] == "ingest.fold") / len(rec["rows"])
    assert read("fold_ms.round")(recorded_run("round")) == pytest.approx(
        base, rel=1e-12)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_a_reader_reads_nothing_once_the_ring_dropped_window_spans(
        ring, monkeypatch, metric):
    """A full ring whose oldest span closed after the window opened has
    dropped spans of the window: the reader gives None rather than a
    share of them.  A full ring that still reaches back before the window
    reads as usual."""
    from repro import obs
    which = READERS[metric][0]
    rec = REC[which]
    lo = round(rec["rows"][0][0] * 1e9)
    before = ["warmup", lo - 2_000, lo - 1_000, None, {}, -1]
    monkeypatch.setattr(obs, "CAPACITY", len(rec["program_spans"]) + 1)
    ring([before] + rec["program_spans"])
    assert read(metric)(recorded_run(which)) is not None
    monkeypatch.setattr(obs, "CAPACITY", len(rec["program_spans"]))
    ring(rec["program_spans"])
    assert read(metric)(recorded_run(which)) is None


def test_the_recorded_clock_map_keeps_each_round_inside_its_step():
    rec = REC["round"]
    run = recorded_run("round")
    off = PS.trace_offset(run.trace, rec["rows"], "round")
    assert off is not None
    anchors = sorted((s, e) for n, s, e in run.trace.spans
                     if n == "cb.round")
    tol = PS.MAX_SPREAD_NS / 2
    for (s, e), row in zip(anchors, rec["rows"]):
        assert round(row[0] * 1e9) + off <= s + tol
        assert e <= round(row[1] * 1e9) + off + tol


def _anchored(late_open_ns, early_close_ns, drift_ns=0):
    """Steps a second apart and their ``cb.round`` spans on a clock 5 s
    behind, each opened late and closed early by its own amount, and the
    clock drifting by ``drift_ns`` a step."""
    rows = [(10.0 + i, 10.5 + i, 0.1) for i in range(len(late_open_ns))]
    spans = [("cb.window", 0, 10 ** 12)] + [
        ("cb.round",
         round(r[0] * 1e9) - 5_000_000_000 + i * drift_ns + a,
         round(r[1] * 1e9) - 5_000_000_000 + i * drift_ns - b)
        for i, (r, a, b) in enumerate(zip(rows, late_open_ns,
                                          early_close_ns))]
    return TR.Trace({"/device:TPU:0": []}, spans), rows


@pytest.mark.parametrize("late, early, drift, want", [
    ([2_000, 2_000, 2_000], [1_000, 1_000, 1_000], 0,
     -5_000_000_000 + 500),
    # one side pins the offset when the other is late: 129 µs late opens
    ([3_000, 129_000, 11_000, 0], [7_400, 7_500, 6_300, 8_000], 0,
     -5_000_000_000 - 3_150),
    ([60_000, 45_000], [30_000, 20_000], 0, None),      # never tight
    ([1_000] * 4, [1_000] * 4, 30_000, None),           # clocks drift
])
def test_the_clock_map_keeps_each_span_in_its_step(late, early, drift,
                                                   want):
    trace, rows = _anchored(late, early, drift)
    assert PS.trace_offset(trace, rows, "round") == want


def test_the_clock_map_refuses_unpaired_steps():
    trace, rows = _anchored([0, 0, 0], [0, 0, 0])
    assert PS.trace_offset(trace, rows[:2], "round") is None
    assert PS.trace_offset(trace, rows, "submit") is None


def test_idle_inside_share_on_a_toy_trace():
    # window 0–100; busy [10,40] [60,70]; idle [0,10] [40,60] [70,100]
    trace = TR.Trace({"/device:TPU:0": [("a", 10, 40), ("b", 60, 70)]},
                     [("cb.window", 0, 100)])
    seal = [("ingest.fold", 5, 50), ("round.stage", 45, 65),
            ("round.stage", 90, 130)]
    # inside: [5,10] 5 + [40,60] 20 + [90,100] 10 = 35 of 60 idle
    assert PS.idle_inside_share(trace, seal) == pytest.approx(35 / 60)
    assert PS.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
