"""Continuous-batching server: batched outputs must equal per-request
sequential greedy generation, including mixed prompt lengths and
mid-flight admission."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serve
from repro.configs import get_config
from repro.models import model as M
from repro.serve.server import BatchedServer, Request, ServerConfig


@pytest.fixture(scope="module")
def model():
    cfg = get_config("granite-3-2b").reduced()
    import dataclasses
    cfg = dataclasses.replace(cfg, dtype="float32", remat=False)
    params = M.init_params(cfg, jax.random.PRNGKey(3))
    return cfg, params


def test_batched_equals_sequential(model):
    cfg, params = model
    key = jax.random.PRNGKey(7)
    prompts = [
        jax.random.randint(jax.random.PRNGKey(i), (L,), 0, cfg.vocab_size)
        for i, L in enumerate([5, 9, 7])
    ]
    # sequential reference
    ref = [np.asarray(serve.greedy_generate(
        cfg, params, p[None, :], 6, max_seq=64))[0] for p in prompts]
    # batched server
    srv = BatchedServer(cfg, params, ServerConfig(n_slots=3, max_seq=64))
    reqs = [Request(rid=i, prompt=p, max_new=6)
            for i, p in enumerate(prompts)]
    out = srv.run(reqs)
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(out[i]), ref[i])


@pytest.mark.slow
def test_more_requests_than_slots(model):
    cfg, params = model
    prompts = [jax.random.randint(jax.random.PRNGKey(10 + i), (4 + i,), 0,
                                  cfg.vocab_size) for i in range(5)]
    ref = [np.asarray(serve.greedy_generate(
        cfg, params, p[None, :], 4, max_seq=48))[0] for p in prompts]
    srv = BatchedServer(cfg, params, ServerConfig(n_slots=2, max_seq=48))
    out = srv.run([Request(rid=i, prompt=p, max_new=4)
                   for i, p in enumerate(prompts)])
    for i in range(5):
        np.testing.assert_array_equal(np.asarray(out[i]), ref[i])


def test_encoder_rejected():
    cfg = get_config("hubert-xlarge").reduced()
    with pytest.raises(AssertionError):
        BatchedServer(cfg, {}, ServerConfig())


# -- slot-lifecycle regressions -------------------------------------------


def _first_tokens(cfg, params, prompt, n, max_seq=64):
    """Reference greedy continuation (first ``n`` tokens)."""
    return np.asarray(serve.greedy_generate(
        cfg, params, prompt[None, :], n, max_seq=max_seq))[0]


def test_max_new_one_terminates_at_prefill(model):
    """A max_new=1 request finishes AT prefill: exactly one token (the
    regression emitted two) and no slot is ever occupied."""
    cfg, params = model
    prompt = jax.random.randint(jax.random.PRNGKey(21), (6,), 0,
                                cfg.vocab_size)
    ref = _first_tokens(cfg, params, prompt, 1)
    srv = BatchedServer(cfg, params, ServerConfig(n_slots=2, max_seq=64))
    req = Request(rid=0, prompt=prompt, max_new=1)
    assert srv.submit(req)
    assert req.done and len(req.out) == 1
    assert req.out[0] == int(ref[0])
    assert srv.free_slots() == [0, 1], "prefill-terminated request held a slot"
    assert srv.step() == 0


def test_eos_as_first_token_terminates_at_prefill(model):
    """If prefill's token IS the EOS, the request never occupies a slot."""
    cfg, params = model
    prompt = jax.random.randint(jax.random.PRNGKey(22), (5,), 0,
                                cfg.vocab_size)
    eos = int(_first_tokens(cfg, params, prompt, 1)[0])
    srv = BatchedServer(cfg, params,
                        ServerConfig(n_slots=2, max_seq=64, eos_id=eos))
    req = Request(rid=0, prompt=prompt, max_new=8)
    assert srv.submit(req)
    assert req.done and req.out == [eos]
    assert srv.free_slots() == [0, 1]


def test_slot_reuse_after_eos(model):
    """A slot freed by mid-decode EOS is immediately reusable, and the
    reused slot's output is untouched by the previous occupant."""
    cfg, params = model
    p0 = jax.random.randint(jax.random.PRNGKey(23), (5,), 0, cfg.vocab_size)
    p1 = jax.random.randint(jax.random.PRNGKey(24), (7,), 0, cfg.vocab_size)
    ref0 = _first_tokens(cfg, params, p0, 4)
    eos = int(ref0[2])                     # stop p0 at its third token
    ref1 = _first_tokens(cfg, params, p1, 4)
    assume_distinct = [int(t) for t in ref1[:4]]
    if eos in assume_distinct:             # measure-zero with random params
        pytest.skip("reference streams collide on the chosen EOS id")
    srv = BatchedServer(cfg, params,
                        ServerConfig(n_slots=1, max_seq=64, eos_id=eos))
    out = srv.run([Request(rid=0, prompt=p0, max_new=8),
                   Request(rid=1, prompt=p1, max_new=4)])
    assert out[0] == [int(t) for t in ref0[:3]]       # truncated at EOS
    assert out[1] == [int(t) for t in ref1[:4]]       # full, same slot
    assert srv.admitted_order == [0, 1]


def test_full_pool_admission_and_refill_order(model):
    """With the pool full, waiting requests are admitted in FIFO order as
    slots free — continuous refill, no reordering, correct outputs."""
    cfg, params = model
    prompts = [jax.random.randint(jax.random.PRNGKey(30 + i), (3 + i,), 0,
                                  cfg.vocab_size) for i in range(5)]
    max_new = [3, 1, 2, 3, 1]
    refs = [_first_tokens(cfg, params, p, n)
            for p, n in zip(prompts, max_new)]
    srv = BatchedServer(cfg, params, ServerConfig(n_slots=2, max_seq=64))
    out = srv.run([Request(rid=i, prompt=p, max_new=n)
                   for i, (p, n) in enumerate(zip(prompts, max_new))])
    assert srv.admitted_order == [0, 1, 2, 3, 4]
    for i in range(5):
        np.testing.assert_array_equal(np.asarray(out[i]), refs[i])
    assert srv.free_slots() == [0, 1]


def test_submit_full_pool_returns_false(model):
    cfg, params = model
    srv = BatchedServer(cfg, params, ServerConfig(n_slots=1, max_seq=64))
    p = jax.random.randint(jax.random.PRNGKey(40), (4,), 0, cfg.vocab_size)
    assert srv.submit(Request(rid=0, prompt=p, max_new=5))
    assert not srv.submit(Request(rid=1, prompt=p, max_new=5))


def test_mixed_lengths_zero_new_prefill_compiles(model):
    """Second pass over a mixed-prompt-length stream compiles nothing:
    prompts bucket to power-of-two padded lengths, so the compile count
    is the bucket count, not the distinct-length count."""
    cfg, params = model
    srv = BatchedServer(cfg, params,
                        ServerConfig(n_slots=2, max_seq=64, min_bucket=8))
    assert srv.bucketed

    def stream(seed, lengths):
        return [Request(rid=i, prompt=jax.random.randint(
            jax.random.PRNGKey(seed + i), (L,), 0, cfg.vocab_size),
            max_new=2) for i, L in enumerate(lengths)]

    srv.run(stream(100, [3, 5, 9, 17, 33]))    # buckets 8, 8, 16, 32, 64
    n0 = srv.prefill_compiles()
    assert n0 <= 4, f"bucketing failed to bound compiles: {n0}"
    srv.run(stream(200, [4, 7, 11, 20, 40, 6, 15]))   # same buckets again
    assert srv.prefill_compiles() == n0, \
        "second mixed-length pass triggered new prefill compiles"


def test_bucketed_prefill_matches_exact(model):
    """Padded masked prefill is numerically the exact-length prefill:
    the batched outputs still equal sequential greedy generation."""
    cfg, params = model
    prompt = jax.random.randint(jax.random.PRNGKey(50), (11,), 0,
                                cfg.vocab_size)
    ref = _first_tokens(cfg, params, prompt, 5)
    srv = BatchedServer(cfg, params, ServerConfig(n_slots=1, max_seq=64))
    out = srv.run([Request(rid=0, prompt=prompt, max_new=5)])
    np.testing.assert_array_equal(np.asarray(out[0]), ref)


# -- FedPFT-as-a-service ---------------------------------------------------


def _service_session(n_classes=3, capacity=16, cache=None):
    from repro.core import gmm as G
    from repro.fl.api import FedSession, GMMSummarizer
    from repro.fl.ingest import IngestConfig
    return FedSession(n_classes=n_classes,
                      summarizer=GMMSummarizer(G.GMMConfig(2, "diag")),
                      ingest=IngestConfig(capacity=capacity, chunk_size=4),
                      program_cache=cache)


def _make_service(model, cache=None, **kw):
    from repro.serve.service import FedPFTService, ServiceConfig
    cfg, params = model
    sess = _service_session(cache=cache)
    return FedPFTService(cfg, params, sess,
                         ServiceConfig(n_slots=4, max_seq=32, **kw))


def _extract_cohort(svc, rng, n_clients=3, n_per=12, n_classes=3):
    """Client datasets whose features come through the SERVICE."""
    reqs = {c: [svc.submit_extract(rng.integers(
        1, svc.cfg.vocab_size, size=int(rng.integers(3, 20))))
        for _ in range(n_per)] for c in range(n_clients)}
    svc.drain()
    return [(jnp.stack([jnp.asarray(r.feats) for r in reqs[c]]),
             jnp.asarray(rng.integers(0, n_classes, size=n_per)))
            for c in range(n_clients)]


@pytest.mark.slow
def test_service_head_bit_identical_to_offline(model):
    """The service round — extraction through the slot pool, GMM wire
    messages through the broker, close via the AOT program cache — trains
    the SAME head, bit for bit, as the offline
    ``FedSession(ingest=, program_cache=).run`` on the same cohort."""
    from repro.launch.aot_cache import ProgramCache
    svc = _make_service(model, cache=ProgramCache())
    rng = np.random.default_rng(11)
    datasets = _extract_cohort(svc, rng)
    svc.warmup(d=datasets[0][0].shape[-1])

    key = jax.random.PRNGKey(9)
    keys = jax.random.split(key, len(datasets) + 1)
    for i, (feats, labels) in enumerate(datasets):
        msg = svc.session.client_update(keys[1 + i], feats, labels, i)
        assert svc.submit_update(i, msg) == "admitted"
    misses0 = svc.session.program_cache.misses
    res_svc = svc.close_round(keys[0])
    assert svc.session.program_cache.misses == misses0, \
        "warmed service round compiled in the request path"

    offline = _service_session(cache=ProgramCache())
    res_off = offline.run(key, datasets)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), res_svc.model, res_off.model)
    assert res_svc.info["comm_bytes"] == res_off.info["comm_bytes"]


@pytest.mark.slow
def test_service_interleaved_extract_infer(model):
    """After the first round, both traffic classes run interleaved through
    the shared slot pool; inference labels equal the head's argmax on the
    request's own features, and extraction for round 2 is unaffected."""
    from repro.core import head as H
    svc = _make_service(model)
    rng = np.random.default_rng(12)
    datasets = _extract_cohort(svc, rng)
    key = jax.random.PRNGKey(10)
    keys = jax.random.split(key, len(datasets) + 1)
    for i, (feats, labels) in enumerate(datasets):
        svc.submit_update(i, svc.session.client_update(
            keys[1 + i], feats, labels, i))
    svc.close_round(keys[0])

    ext = [svc.submit_extract(rng.integers(1, svc.cfg.vocab_size,
                                           size=int(rng.integers(3, 20))))
           for _ in range(6)]
    inf = [svc.submit_infer(rng.integers(1, svc.cfg.vocab_size,
                                         size=int(rng.integers(3, 20))))
           for _ in range(6)]
    svc.drain()
    assert all(r.done for r in ext + inf)
    assert all(r.feats is not None for r in ext)
    for r in inf:
        f = svc._feats(svc.params,
                       jnp.asarray(r.tokens)[None, :],
                       jnp.asarray([r.tokens.shape[0]]))
        want = int(jnp.argmax(H.head_logits(svc.head, f), axis=-1)[0])
        assert r.label == want
    st = svc.stats()
    assert st["extract"]["n"] >= 6 and st["infer"]["n"] == 6
    assert st["infer"]["p99_us"] >= st["infer"]["p50_us"] >= 0


def test_service_requires_ingest(model):
    from repro.core import gmm as G
    from repro.fl.api import FedSession, GMMSummarizer
    from repro.serve.service import FedPFTService
    cfg, params = model
    sess = FedSession(n_classes=3,
                      summarizer=GMMSummarizer(G.GMMConfig(2, "diag")))
    with pytest.raises(ValueError, match="ingest"):
        FedPFTService(cfg, params, sess)


def test_service_infer_needs_head(model):
    svc = _make_service(model)
    with pytest.raises(RuntimeError, match="close_round"):
        svc.submit_infer(np.arange(1, 5))
    assert svc.rejected_no_head == 1


def test_service_guaranteed_extract_share(model):
    """With both queues backed up, one step admits ceil(share·B) extract
    rows and fills the rest with inference — neither class starves."""
    svc = _make_service(model, extract_share=0.5)
    svc.head = {"w": jnp.zeros((svc.cfg.d_model, 3), jnp.float32),
                "b": jnp.zeros((3,), jnp.float32)}
    rng = np.random.default_rng(13)
    for _ in range(8):
        svc.submit_extract(rng.integers(1, svc.cfg.vocab_size, size=5))
        svc.submit_infer(rng.integers(1, svc.cfg.vocab_size, size=5))
    done = svc.step()
    assert done == 4
    st = svc.stats()
    assert st["extract"]["n"] == 2 and st["infer"]["n"] == 2


def test_service_feature_compiles_bounded(model):
    """Traffic with many distinct prompt lengths compiles one feature
    step per power-of-two bucket, and a second wave compiles nothing."""
    svc = _make_service(model)
    rng = np.random.default_rng(14)
    for L in (3, 5, 9, 11, 17, 19):
        svc.submit_extract(rng.integers(1, svc.cfg.vocab_size, size=L))
    svc.drain()
    n0 = svc.feature_compiles()
    assert n0 <= 3                      # buckets 8, 16, 32
    for L in (4, 6, 10, 12, 18, 20):
        svc.submit_extract(rng.integers(1, svc.cfg.vocab_size, size=L))
    svc.drain()
    assert svc.feature_compiles() == n0


@pytest.mark.slow
def test_service_round_sanitized(model, sanitized):
    """The whole serve→ingest→train→infer loop runs clean under the
    runtime sanitizer (debug_nans + key-reuse tracer)."""
    svc = _make_service(model)
    rng = np.random.default_rng(15)
    datasets = _extract_cohort(svc, rng, n_clients=2, n_per=8)
    key = jax.random.PRNGKey(16)
    keys = jax.random.split(key, len(datasets) + 1)
    for i, (feats, labels) in enumerate(datasets):
        svc.submit_update(i, svc.session.client_update(
            keys[1 + i], feats, labels, i))
    svc.close_round(keys[0])
    r = svc.submit_infer(rng.integers(1, svc.cfg.vocab_size, size=6))
    svc.drain()
    assert r.done and r.label is not None


@pytest.mark.slow
def test_service_spans_split_close_round_and_step(model):
    """The recorder splits a warmed round's ``service.close_round`` into
    fold, stage, program and install, which lie inside it and cover at
    least 90% of it; a serving step holds admit, dispatch and readback in
    that order; the feature step's work reaches ``stats()`` and the staged
    bytes the round's ``info``."""
    import time
    from repro import obs
    from repro.launch.aot_cache import ProgramCache
    svc = _make_service(model, cache=ProgramCache())
    rng = np.random.default_rng(17)
    datasets = _extract_cohort(svc, rng)
    svc.warmup(d=datasets[0][0].shape[-1])
    key = jax.random.PRNGKey(18)
    keys = jax.random.split(key, len(datasets) + 1)
    msgs = [svc.session.client_update(keys[1 + i], f, y, i)
            for i, (f, y) in enumerate(datasets)]
    t0 = time.perf_counter_ns()
    for i, m in enumerate(msgs):
        assert svc.submit_update(i, m) == "admitted"
    res = svc.close_round(keys[0])
    svc.submit_extract(rng.integers(1, svc.cfg.vocab_size, size=7))
    svc.submit_infer(rng.integers(1, svc.cfg.vocab_size, size=5))
    assert svc.step() == 2

    recs = obs.spans(since_ns=t0)
    by_seq = {r.seq: r for r in recs}
    submits = [r for r in recs if r.name == "ingest.submit"]
    assert [r.ids["client"] for r in submits] == [0, 1, 2]
    close = [r for r in recs if r.name == "service.close_round"]
    assert len(close) == 1 and close[0].ids == {"round": 0}
    close = close[0]
    parts = {"ingest.fold", "round.stage", "round.program",
             "service.install"}
    inside = [r for r in recs if r.name in parts]
    assert {r.name for r in inside} == parts
    for r in inside:
        assert close.t0_ns <= r.t0_ns <= r.t1_ns <= close.t1_ns, r.name
        assert r.ids["round"] == 0
        # each lies under close_round, directly or through the session
        p = r.parent
        while p is not None and p != close.seq:
            p = by_seq[p].parent
        assert p == close.seq, r.name
    covered = sum(r.t1_ns - r.t0_ns for r in inside)
    assert covered >= 0.9 * (close.t1_ns - close.t0_ns), \
        (covered, close.t1_ns - close.t0_ns)

    step = [r for r in recs if r.name == "service.step"][-1]
    kids = sorted((r for r in recs if r.parent == step.seq),
                  key=lambda r: r.t0_ns)
    assert [r.name for r in kids] == ["service.admit", "service.dispatch",
                                      "service.readback"]
    assert all(r.ids == step.ids for r in kids)
    for a, b in zip(kids, kids[1:]):
        assert a.t1_ns <= b.t0_ns
    st = svc.stats()
    assert st["real_tokens"] == sum(r.tokens.shape[0] for reqs in
                                    svc.completed.values() for r in reqs)
    assert st["positions_computed"] % 4 == 0
    assert st["positions_computed"] > st["real_tokens"]
    # the staged stack: f32 π, μ, σ² at the canonical slot count, then
    # int32 labels and the counts (int32 or int64)
    M, _, K, d = res.info["compile"]["canonical"][:4]
    floats = 4 * M * K * (1 + 2 * d)
    assert floats + 8 * M <= res.info["staged_bytes"] <= floats + 12 * M


def test_serve_dir_lint_clean():
    """`python -m repro.analysis src/repro/serve` gates clean — the serve
    layer holds the same hygiene bar as the rest of the tree."""
    import pathlib
    from repro.analysis import analyze_paths, gating
    root = pathlib.Path(__file__).resolve().parents[1]
    fs = analyze_paths([str(root / "src" / "repro" / "serve")],
                       semantic=False)
    assert gating(fs) == [], "\n".join(f.format() for f in gating(fs))


# -- deadline admission control (DESIGN.md §13) -----------------------------


def _deadline_service(model, t, deadline_s=10.0, **kw):
    """A service on a fake clock whose session broker has a deadline."""
    from repro.core import gmm as G
    from repro.fl.api import FedSession, GMMSummarizer
    from repro.fl.ingest import IngestConfig
    from repro.serve.service import FedPFTService, ServiceConfig
    cfg, params = model
    sess = FedSession(n_classes=3,
                      summarizer=GMMSummarizer(G.GMMConfig(2, "diag")),
                      ingest=IngestConfig(capacity=16, chunk_size=4,
                                          deadline_s=deadline_s))
    return FedPFTService(cfg, params, sess,
                         ServiceConfig(n_slots=4, max_seq=32, **kw),
                         clock=lambda: t["now"])


def test_service_sheds_extract_near_deadline(model):
    from repro.serve.service import AdmissionError
    t = {"now": 0.0}
    svc = _deadline_service(model, t, deadline_s=10.0,
                            deadline_guard_s=3.0)
    rng = np.random.default_rng(21)
    prompt = rng.integers(1, svc.cfg.vocab_size, size=5)
    assert svc.submit_extract(prompt).kind == "extract"   # plenty of time
    t["now"] = 8.0                                        # 2s left < guard
    with pytest.raises(AdmissionError, match="deadline_guard"):
        svc.submit_extract(prompt)
    assert svc.stats()["shed_extracts"] == 1
    assert len(svc.queues["extract"]) == 1                # nothing parked


def test_service_defers_extract_to_next_round(model):
    t = {"now": 0.0}
    svc = _deadline_service(model, t, deadline_s=10.0,
                            deadline_guard_s=3.0, extract_admission="defer")
    rng = np.random.default_rng(22)
    datasets = _extract_cohort(svc, rng, n_clients=2, n_per=8)
    key = jax.random.PRNGKey(23)
    keys = jax.random.split(key, 3)
    for i, (feats, labels) in enumerate(datasets):
        assert svc.submit_update(i, svc.session.client_update(
            keys[1 + i], feats, labels, i)) == "admitted"
    t["now"] = 9.0
    late_req = svc.submit_extract(rng.integers(1, svc.cfg.vocab_size,
                                               size=6))
    assert late_req.deferred and not svc.queues["extract"]
    st = svc.stats()
    assert st["deferred_extracts"] == 1 and st["deferred_pending"] == 1
    svc.close_round(keys[0])
    # the parked request re-entered the new round's queue
    assert [r.rid for r in svc.queues["extract"]] == [late_req.rid]
    svc.drain()
    assert late_req.done and late_req.feats is not None
    assert svc.stats()["deferred_pending"] == 0


@pytest.mark.slow
def test_service_partial_round_matches_offline_survivors(model):
    """Stragglers and corrupt payloads degrade the service round; the
    head it serves equals — bitwise — the offline session fed only the
    admitted clients, and every submitted byte lands in one verdict."""
    import dataclasses as _dc
    t = {"now": 0.0}
    svc = _deadline_service(model, t, deadline_s=10.0)
    rng = np.random.default_rng(24)
    datasets = _extract_cohort(svc, rng, n_clients=4, n_per=8)
    key = jax.random.PRNGKey(25)
    keys = jax.random.split(key, len(datasets) + 1)
    msgs = [svc.session.client_update(keys[1 + i], f, y, i)
            for i, (f, y) in enumerate(datasets)]
    assert svc.submit_update(0, msgs[0]) == "admitted"
    assert svc.submit_update(1, msgs[1]) == "admitted"
    bad = _dc.replace(msgs[2], payload=msgs[2].payload[:-5])
    assert svc.submit_update(2, bad) == "quarantined"     # corrupt in flight
    t["now"] = 11.0
    assert svc.submit_update(3, msgs[3]) == "late"        # straggler
    acct = svc.broker.accounting()
    assert acct["admitted_bytes"] + acct["quarantined_bytes"] \
        + acct["late_bytes"] == acct["sent_bytes"]
    res = svc.close_round(keys[0])
    assert res.info["faults"]["degraded"]
    # a fresh broker opened: the straggler is welcome in the NEXT round
    assert svc.submit_update(3, msgs[3]) == "admitted"

    from repro.fl.ingest import IngestBroker, IngestConfig
    off = IngestBroker(IngestConfig(capacity=16, chunk_size=4), 3,
                       clock=lambda: 0.0)
    off.submit(0, msgs[0])
    off.submit(1, msgs[1])
    res_off = svc.session.aggregate_from_broker(keys[0], off)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), res.model, res_off.model)
