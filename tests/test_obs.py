"""The flight recorder (repro.obs): spans nest per thread and carry their
ids down, the ring is bounded, threads lose no record, reset empties it,
and a span is visible to a running profiler session."""
import glob
import os
import threading

import pytest

from repro import obs


@pytest.fixture(autouse=True)
def empty():
    obs.reset()
    yield
    obs.reset()


def _by_name():
    return {r.name: r for r in obs.spans()}


def test_children_nest_inside_their_parent():
    with obs.span("outer") as outer:
        with obs.span("mid"):
            with obs.span("inner"):
                pass
        with obs.span("sibling"):
            pass
    got = _by_name()
    assert outer.elapsed_ns == got["outer"].t1_ns - got["outer"].t0_ns
    assert got["outer"].parent is None
    assert got["mid"].parent == got["sibling"].parent == got["outer"].seq
    assert got["inner"].parent == got["mid"].seq
    for child, parent in (("mid", "outer"), ("inner", "mid"),
                          ("sibling", "outer")):
        assert got[parent].t0_ns <= got[child].t0_ns \
            <= got[child].t1_ns <= got[parent].t1_ns
    # the ring holds spans in the order they closed
    assert [r.name for r in obs.spans()] == ["inner", "mid", "sibling",
                                             "outer"]


def test_ids_propagate_to_children_and_merge():
    with obs.span("service.close_round", round=4):
        with obs.span("ingest.fold"):
            pass
        with obs.span("ingest.submit", client=7):
            with obs.span("leaf"):
                pass
    with obs.span("alone"):
        pass
    got = _by_name()
    assert got["service.close_round"].ids == {"round": 4}
    assert got["ingest.fold"].ids == {"round": 4}
    assert got["ingest.submit"].ids == {"round": 4, "client": 7}
    assert got["leaf"].ids == {"round": 4, "client": 7}
    assert got["alone"].ids == {}


def test_threads_keep_their_own_stacks():
    seen = {}

    def work():
        with obs.span("worker", client=1) as w:
            seen["seq"] = w.seq

    with obs.span("main", step=0):
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    got = _by_name()
    assert got["worker"].parent is None and got["worker"].ids == {
        "client": 1}
    assert got["worker"].seq == seen["seq"]


def test_a_span_closes_when_its_block_raises():
    with pytest.raises(ValueError):
        with obs.span("outer"):
            with obs.span("fails"):
                raise ValueError("x")
    with obs.span("after"):
        pass
    got = _by_name()
    assert got["fails"].parent == got["outer"].seq
    assert got["after"].parent is None


def test_the_ring_is_bounded_and_keeps_the_newest():
    n = obs.CAPACITY + 10
    for i in range(n):
        with obs.span("s", step=i):
            pass
    recs = obs.spans()
    assert len(recs) == obs.CAPACITY
    assert recs[0].ids == {"step": 10} and recs[-1].ids == {"step": n - 1}


def test_spans_since_a_time():
    with obs.span("early"):
        pass
    cut = obs.spans()[-1].t1_ns + 1
    with obs.span("late"):
        pass
    assert [r.name for r in obs.spans(since_ns=cut)] == ["late"]


def test_reset_empties_the_ring():
    with obs.span("s"):
        pass
    obs.spans().clear()                   # a read is a copy
    assert [r.name for r in obs.spans()] == ["s"]
    obs.reset()
    assert obs.spans() == []


def test_spans_of_many_threads_are_all_kept():
    """No record is lost and no stack is shared when many threads open
    spans at once, with the interpreter switching threads as often as it
    can."""
    import sys

    def work(i):
        for _ in range(500):
            with obs.span("outer", client=i):
                with obs.span("inner"):
                    pass
    keep = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(keep)
    assert not any(t.is_alive() for t in threads)
    recs = obs.spans()
    assert len(recs) == 16 * 500 * 2
    by_seq = {r.seq: r for r in recs}
    assert len(by_seq) == len(recs)
    for r in recs:
        if r.name == "inner":
            parent = by_seq[r.parent]
            assert parent.name == "outer" and parent.ids == r.ids
        else:
            assert r.parent is None


def test_a_profiler_session_sees_the_span(tmp_path):
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("obs.test_span", step=1):
            jax.block_until_ready(jax.numpy.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert paths
    names = {e.name for p in paths
             for plane in ProfileData.from_file(p).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert "obs.test_span" in names
    assert [r.name for r in obs.spans()] == ["obs.test_span"]
