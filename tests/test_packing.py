"""Packed feature extraction: several prompts share a row of the feature
step, each attending and pooling only within its own segment.

The packed step must give every request ``model.features`` of its own
unpadded prompt, within float32 rounding.  Rope is relative, so rope
positions that run on across a row's segments change a segment's features
only through the rounding of the rotation angles, which grows with the
position: the planted faults run on a row whose short segments sit 8,100
positions in, where that rounding is some 25 times the packed step's
own error.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serve
from repro.configs import get_config
from repro.models import layers as LY
from repro.models import model as M

TOL = 3e-6          # relative L2 per feature row, float32 model
# zamba2 packed against its own unpacked call, per feature row: float32
# differs in the SSD's summation order across chunk boundaries (≈1e-6);
# bfloat16 also rounds the chunked sums differently where a segment's
# chunks are cut elsewhere (≈1e-3).  A state not reset reads 0.06–0.09
# in bfloat16.
ZAMBA2_TOL = {"float32": 1e-5, "bfloat16": 0.01}


@pytest.fixture(scope="module")
def dense():
    cfg = dataclasses.replace(
        get_config("granite-3-2b").reduced(), dtype="float32", remat=False,
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=1, head_dim=32,
        d_ff=128)
    assert cfg.family == "dense"
    return cfg, M.init_params(cfg, jax.random.PRNGKey(3))


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=L) for L in lengths]


@pytest.fixture(scope="module")
def unpadded(dense):
    """``model.features`` of each prompt of a case alone, unpadded."""
    cfg, params = dense
    memo = {}

    def features(case):
        if case not in memo:
            memo[case] = np.stack([np.asarray(M.features(
                cfg, params, {"tokens": jnp.asarray(p)[None]}))[0]
                for p in _prompts(cfg, CASES[case][0])])
        return memo[case]
    return features


def _row_errors(got, want):
    return (np.linalg.norm(got - want, axis=1)
            / np.linalg.norm(want, axis=1))


# (lengths, width, n_slots): rows the packer lays out
CASES = {
    # one row of many short segments after a long one
    "many_short": ([20, 3, 5, 2, 7, 4, 6, 1, 9], 64, 16),
    # a request as wide as the row, alone in it, beside a row of shorts
    "full_width": ([64, 30, 12, 5], 64, 4),
    # three packed rows rounded up to four (one all padding); five of the
    # eight columns are empty
    "empty_rows": ([40, 35, 30], 64, 8),
    # short segments far into a wide row
    "deep": ([8100, 30, 6, 3, 17], 8192, 8),
}


def _packed_features(cfg, params, lengths, width, n_slots,
                     corrupt=lambda length: length):
    tokens, length = serve.pack_batch(_prompts(cfg, lengths), width, n_slots)
    feats = jax.jit(serve.make_feature_step(cfg))(
        params, jnp.asarray(tokens), jnp.asarray(corrupt(length)))
    return tokens, length, np.asarray(feats)


@pytest.mark.parametrize("case", sorted(CASES))
def test_packed_step_equals_unpadded_features(dense, unpadded, case):
    cfg, params = dense
    lengths, width, n_slots = CASES[case]
    tokens, length, got = _packed_features(cfg, params, *CASES[case])
    assert got.shape == (n_slots, cfg.d_model)
    assert np.isfinite(got).all()
    n = len(lengths)
    assert _row_errors(got[:n], unpadded(case)).max() < TOL
    np.testing.assert_array_equal(got[n:], 0.0)       # empty columns
    if case == "empty_rows":
        assert tokens.shape[0] == 4 and not length[3:].any()


def _drop_segment_mask(monkeypatch):
    real = LY._sdpa_chunked
    monkeypatch.setattr(LY, "_sdpa_chunked",
                        lambda *a, segment_ids=None, **kw: real(*a, **kw))


def _rope_from_row_start(monkeypatch):
    real = LY.apply_rope
    monkeypatch.setattr(
        LY, "apply_rope", lambda x, positions, theta: real(
            x, jnp.arange(x.shape[1]) if positions.ndim == 2 else positions,
            theta))


@pytest.mark.parametrize("fault", ["lengths_off_by_one", "mask_dropped",
                                   "rope_not_reset"])
def test_packed_comparison_catches_planted_faults(dense, unpadded,
                                                  monkeypatch, fault):
    cfg, params = dense
    corrupt = lambda length: length
    if fault == "lengths_off_by_one":
        corrupt = lambda length: np.where(length > 1, length - 1, length)
    elif fault == "mask_dropped":
        _drop_segment_mask(monkeypatch)
    else:
        _rope_from_row_start(monkeypatch)
    lengths, width, n_slots = CASES["deep"]
    *_, got = _packed_features(cfg, params, lengths, width, n_slots,
                               corrupt)
    err = _row_errors(got[:len(lengths)], unpadded("deep"))
    assert err.max() > TOL, (fault, err)


# -- the packer -------------------------------------------------------------


def _segments(tokens, length):
    """Each column's (row, tokens), read back by walking the rows."""
    out = {}
    for r in range(tokens.shape[0]):
        at = 0
        for g in range(length.shape[1]):
            L = int(length[r, g])
            if L:
                assert g not in out, f"column {g} in two rows"
                out[g] = (r, tokens[r, at:at + L])
            at += L
        assert at <= tokens.shape[1], "row wider than the bucket"
        assert not tokens[r, at:].any(), "padding holds tokens"
    return out


@pytest.mark.parametrize("n_slots", [1, 3, 4, 8, 16])
def test_pack_batch_places_every_request_once(n_slots):
    rng = np.random.default_rng(n_slots)
    for _ in range(200):
        n = int(rng.integers(1, n_slots + 1))
        lengths = rng.integers(1, 65, size=n)
        width = serve.pow2_bucket(int(lengths.max()), 8, 64)
        prompts = [rng.integers(1, 1000, size=L) for L in lengths]
        tokens, length = serve.pack_batch(prompts, width, n_slots)
        R = tokens.shape[0]
        assert tokens.shape == (R, width) and length.shape == (R, n_slots)
        assert R <= n_slots and (R & (R - 1) == 0 or R == n_slots)
        assert R <= min(n_slots, 1 << (n - 1).bit_length())
        segs = _segments(tokens, length)
        assert sorted(segs) == list(range(n))
        for i, p in enumerate(prompts):
            np.testing.assert_array_equal(segs[i][1], p)
        again = serve.pack_batch(prompts, width, n_slots)
        np.testing.assert_array_equal(again[0], tokens)
        np.testing.assert_array_equal(again[1], length)


def test_pack_rows_is_first_fit_decreasing():
    # 30 → row 0, 25 → row 1 (30 + 25 > 32), 7 → row 1, 2 → row 0
    assert serve.pack_rows([7, 30, 2, 25], 32, 8) == ([1, 0, 0, 1], 2)
    assert serve.pack_rows([32] * 3, 32, 8) == ([0, 1, 2], 4)
    assert serve.pack_rows([32] * 3, 32, 3) == ([0, 1, 2], 3)
    with pytest.raises(ValueError, match="width"):
        serve.pack_rows([33], 32, 8)


# -- the service --------------------------------------------------------------


def _service(cfg, params, n_slots=4, max_seq=32):
    from repro.fl.ingest import IngestConfig
    from repro.fl.api import FedSession
    from repro.serve.service import FedPFTService, ServiceConfig
    return FedPFTService(cfg, params, FedSession(
        n_classes=3, ingest=IngestConfig(capacity=16, chunk_size=4)),
        ServiceConfig(n_slots=n_slots, max_seq=max_seq))


def _observe(svc):
    """Record (tokens shape, length shape) of every feature-step call."""
    calls, inner = [], svc._feats

    def observed(params, tokens, length):
        calls.append((tuple(tokens.shape), tuple(length.shape)))
        return inner(params, tokens, length)
    svc._feats = observed
    return calls, inner


@pytest.mark.parametrize("name", ["rwkv6-3b", "granite-moe-3b-a800m"])
def test_recurrent_and_moe_families_keep_one_request_per_row(name):
    cfg = get_config(name).reduced()
    assert cfg.family in ("ssm", "moe")
    step = serve.make_feature_step(cfg)
    assert step.packs is False
    with pytest.raises(ValueError, match="does not pack"):
        step(None, np.zeros((1, 8), np.int32), np.ones((1, 4), np.int32))
    svc = _service(cfg, M.init_params(cfg, jax.random.PRNGKey(0)))
    calls, jitted = _observe(svc)
    for L in (3, 5, 2):
        svc.submit_extract(np.arange(1, L + 1))
    assert svc.step() == 3
    assert calls == [((4, 8), (4,))]
    svc._feats = jitted
    assert svc.stats()["rows_computed"] == 4


@pytest.mark.parametrize("name", ["zamba2-7b", "granite-4-h-micro"])
def test_hybrid_families_pack_rows(name):
    """Both hybrid layouts pack: three prompts of a bucket of 8 take two
    rows (5 + 3, then 2), not ``n_slots``; the engagement counter says
    so."""
    cfg = get_config(name).reduced()
    assert cfg.family == "hybrid"
    step = serve.make_feature_step(cfg)
    assert step.packs is True
    svc = _service(cfg, M.init_params(cfg, jax.random.PRNGKey(0)))
    calls, jitted = _observe(svc)
    for L in (3, 5, 2):
        svc.submit_extract(np.arange(1, L + 1))
    assert svc.step() == 3
    assert calls == [((2, 8), (2, 4))]
    svc._feats = jitted
    st = svc.stats()
    assert st["rows_computed"] == 2 < svc.steps * svc.scfg.n_slots


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_zamba2_packed_service_equals_its_unpacked_step(dtype):
    """zamba2's Mamba-2 stack and shared attention block, packed through
    the service, give each request the features of its own ``(B,)`` call:
    segments shorter than the conv (4) follow others in a row, and
    segments cross the SSD's chunks (32)."""
    cfg = dataclasses.replace(get_config("zamba2-7b").reduced(),
                              dtype=dtype, remat=False)
    assert cfg.conv_width == 4 and cfg.chunk_size == 32
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    svc = _service(cfg, params, n_slots=8, max_seq=128)
    calls, step = _observe(svc)
    lengths = [70, 3, 33, 1, 64, 9, 2, 40]
    reqs = [svc.submit_extract(p) for p in _prompts(cfg, lengths, seed=4)]
    svc.drain()
    assert calls == [((2, 128), (2, 8))]
    got = np.stack([np.asarray(r.feats, np.float32) for r in reqs])
    want = np.stack([np.asarray(step(
        params, jnp.asarray(p)[None], jnp.asarray([len(p)])), np.float32)[0]
        for p in _prompts(cfg, lengths, seed=4)])
    assert _row_errors(got, want).max() < ZAMBA2_TOL[dtype]


def test_service_packs_and_counts_the_shapes_it_calls(dense):
    """A dense service packs: its counts agree with the shapes called, and
    each request's features are the unpacked call's."""
    cfg, params = dense
    svc = _service(cfg, params, n_slots=8, max_seq=64)
    calls, step = _observe(svc)
    lengths = [50, 3, 9, 17, 4, 6, 30, 2, 64, 12, 5, 8]
    reqs = [svc.submit_extract(p) for p in _prompts(cfg, lengths, seed=1)]
    svc.drain()
    # 8 prompts (longest 50) pack into two rows of 64; then 4 (one of 64)
    assert calls == [((2, 64), (2, 8)), ((2, 64), (2, 8))]
    svc._feats = step
    st = svc.stats()
    assert st["feature_compiles"] == 1
    assert st["rows_computed"] == sum(t[0] for t, _ in calls) == 4
    assert st["positions_computed"] == sum(t[0] * t[1] for t, _ in calls)
    assert st["real_tokens"] == sum(lengths)
    got = np.stack([r.feats for r in reqs])
    for lo, hi in ((0, 8), (8, 12)):
        prompts = [r.tokens for r in reqs[lo:hi]]
        width = serve.pow2_bucket(max(map(len, prompts)), 8, 64)
        tokens, length = serve.pad_batch(prompts, width, 8)
        want = np.asarray(step(params, jnp.asarray(tokens),
                               jnp.asarray(length)))[:hi - lo]
        assert _row_errors(got[lo:hi], want).max() < TOL
