"""granite-4-h-micro, the Mamba-2/attention hybrid, against the plain
float32 reference (``repro.models.hybrid_reference``) at a small size on
the CPU: one whole period of 10 layers (attention at index 5), GQA 4/2,
d 256, chunk 32, so T = 40 and 70 cross chunk boundaries without being a
multiple of the chunk.  Planted faults must fail the same comparison, and
the dense configs' features must stay bit-identical to those recorded
before the hybrid's config fields existed."""
import dataclasses
import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serve
from repro.configs import get_config
from repro.fl import ingest as IG
from repro.fl.api import FedSession
from repro.models import hybrid_reference as REF
from repro.models import mamba2 as MM
from repro.models import model as M
from repro.models.layers import rms_norm
from repro.serve.service import FedPFTService, ServiceConfig

GOLDEN = pathlib.Path(__file__).parent / "fixtures" / \
    "dense_features_golden.json"
JAXPRS = pathlib.Path(__file__).parent / "fixtures" / "unpacked_jaxprs.json"

# Relative L2 error of a row of final hidden states (or of a feature row).
# float32 program: the chunked SSD factorises exp(Σ log a) and sums in
# another order than the sequential recurrence, which reads ≈2e-6 at this
# size; 2e-5 leaves 10× room and sits 600× under the smallest planted
# fault (rope on the one NoPE layer reads ≈0.0125).
TOL_F32 = 2e-5
# bfloat16 program (the served type): activations rounded to 2^-8 at each
# of the 10 layers' products read ≈0.034 against the float32 reference of
# the same bfloat16 weights; 0.06 leaves room for other seeds.
TOL_BF16 = 0.06


def small(dtype="float32"):
    cfg = get_config("granite-4-h-micro").reduced(n_layers=10)
    return dataclasses.replace(cfg, n_kv_heads=2, remat=False, dtype=dtype)


def perturbed(params, key):
    """Norm gains and D near 1, conv bias near 0, none exactly: a program
    or reference that skips one of them disagrees."""
    def f(kp, a):
        name = str(getattr(kp[-1], "key", ""))
        k = jax.random.fold_in(key, len(jax.tree_util.keystr(kp)))
        if name in ("ln1", "ln2", "gn", "final_norm", "D"):
            return (a + 0.1 * jax.random.normal(k, a.shape)).astype(a.dtype)
        if name == "conv_b":
            return (0.1 * jax.random.normal(k, a.shape)).astype(a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(f, params)


def make_params(cfg):
    return perturbed(M.init_params(cfg, jax.random.PRNGKey(0)),
                     jax.random.PRNGKey(1))


def row_errors(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def hidden_error(cfg, params, ref_cfg, ref_params, T):
    tok = jax.random.randint(jax.random.PRNGKey(T), (2, T), 1,
                             cfg.vocab_size)
    got = jax.jit(lambda p, t: M.final_hidden(cfg, p, {"tokens": t}))(
        params, tok)
    return row_errors(got, REF.hidden(ref_cfg, ref_params, tok)).max()


@pytest.fixture(scope="module")
def f32():
    cfg = small()
    return cfg, make_params(cfg)


def test_config_holds_the_published_numbers():
    cfg = get_config("granite-4-h-micro")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size) == (
        40, 2048, 32, 8, 64, 8192, 100352)
    assert M.hybrid_counts(cfg) == (36, 4)
    assert [i for i in range(40) if cfg.layer_pattern[i % 10] == "a"] == \
        [5, 15, 25, 35]
    d_inner, H, P, N = MM.mamba_dims(cfg)
    assert (d_inner, H, P, N, cfg.conv_width, cfg.chunk_size) == (
        4096, 64, 64, 128, 4, 256)
    assert (cfg.use_rope, cfg.attn_scale, cfg.embed_scale,
            cfg.residual_scale, cfg.norm_eps) == (False, 0.015625, 12.0,
                                                  0.22, 1e-5)
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    shapes.pop("lm_head")
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 3.18e9 < n < 3.20e9, n


@pytest.mark.parametrize("T", [40, 64, 70])
def test_final_hidden_matches_the_plain_reference(f32, T):
    cfg, params = f32
    assert hidden_error(cfg, params, cfg, params, T) < TOL_F32


def test_served_type_matches_the_plain_reference():
    cfg = small("bfloat16")
    params = make_params(cfg)
    assert hidden_error(cfg, params, cfg, params, 70) < TOL_BF16


# (T, chunk, segment lengths of row 0): row 1 takes them in reverse order.
SSD_CASES = [
    pytest.param(40, 16, None, id="40-16"),
    pytest.param(64, 16, None, id="64-16"),
    pytest.param(7, 16, None, id="7-16"),
    pytest.param(70, 32, None, id="70-32"),
    # starts mid-chunk; the 20 spans the boundary at 16 (and, reversed, 32)
    pytest.param(40, 16, (5, 9, 20, 6), id="40-16-mid_chunk"),
    # segments that end exactly on chunk boundaries
    pytest.param(40, 16, (16, 16, 8), id="40-16-on_boundary"),
    # segments of one token, next to each other
    pytest.param(40, 16, (1, 7, 1, 1, 30), id="40-16-length_one"),
    # a segment over a whole chunk, entered from the chunk before
    pytest.param(64, 16, (3, 40, 21), id="64-16-whole_chunk"),
    # trailing padding, which segment_layout makes one more segment, in a
    # T the chunk does not divide
    pytest.param(70, 32, (33, 2, 20, 15), id="70-32-trailing_pad"),
]


def _segment_rows(lengths, Bt):
    rows = [lengths if b % 2 == 0 else lengths[::-1] for b in range(Bt)]
    return rows, jnp.asarray(np.stack([np.repeat(np.arange(len(r)), r)
                                       for r in rows]))


@pytest.mark.parametrize("T,chunk,lengths", SSD_CASES)
def test_ssd_chunked_is_the_sequential_recurrence(T, chunk, lengths):
    """The chunked form against the recurrence one step at a time; with
    segment ids, against the recurrence over each segment alone."""
    Bt, H, P, N = 2, 3, 4, 5
    ks = jax.random.split(jax.random.PRNGKey(T), 5)
    x = jax.random.normal(ks[0], (Bt, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, T, H)) - 2.0)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (Bt, T, N))
    C = jax.random.normal(ks[4], (Bt, T, N))
    rows, seg = (([(T,)] * Bt, None) if lengths is None
                 else _segment_rows(lengths, Bt))
    xh = x.transpose(0, 2, 1, 3)
    a_log = (dt * A).transpose(0, 2, 1)
    got, S = MM.ssd_chunked(xh * dt.transpose(0, 2, 1)[..., None], a_log,
                            B, C, jnp.zeros((Bt, H, N, P)), chunk=chunk,
                            segment_ids=seg)
    got = np.asarray(got.transpose(0, 2, 1, 3))
    for b, row in enumerate(rows):
        assert sum(row) == T
        at = 0
        for L in row:
            sl = (slice(b, b + 1), slice(at, at + L))
            want = REF.ssd_sequential(x[sl], dt[sl], A, B[sl], C[sl],
                                      jnp.zeros(H))
            np.testing.assert_allclose(got[sl], np.asarray(want),
                                       rtol=2e-5, atol=2e-5)
            at += L
    # the final state continues each row's last segment: one more step
    # agrees
    x1, dt1 = x[:, -1:], dt[:, -1:]
    y1, _ = MM.ssd_decode((x1 * dt1[..., None])[:, 0], (dt1 * A)[:, 0],
                          B[:, -1], C[:, -1], S)
    for b, row in enumerate(rows):
        sl = (slice(b, b + 1), slice(T - row[-1], T))
        whole = REF.ssd_sequential(jnp.concatenate([x[sl], x1[b:b + 1]], 1),
                                   jnp.concatenate([dt[sl], dt1[b:b + 1]], 1),
                                   A, jnp.concatenate([B[sl], B[b:b + 1, -1:]],
                                                      1),
                                   jnp.concatenate([C[sl], C[b:b + 1, -1:]],
                                                   1), jnp.zeros(H))
        np.testing.assert_allclose(np.asarray(y1[b]),
                                   np.asarray(whole[0, -1]),
                                   rtol=2e-5, atol=2e-5)


def _service(cfg, params, n_slots):
    return FedPFTService(cfg, params, FedSession(
        n_classes=2, ingest=IG.IngestConfig()), ServiceConfig(
        n_slots=n_slots, max_seq=128, min_bucket=8))


def _extract(svc, prompts):
    """Every prompt through the service; the feature-step calls' shapes
    and each request's features."""
    calls, inner = [], svc._feats

    def observed(params, tokens, length):
        calls.append((tuple(tokens.shape), tuple(length.shape)))
        return inner(params, tokens, length)
    svc._feats = observed
    reqs = [svc.submit_extract(p) for p in prompts]
    while svc.queues["extract"]:
        svc.step()
    return calls, np.stack([np.asarray(r.feats, np.float32) for r in reqs])


def test_service_extracts_padded_prompts_as_each_alone(f32):
    """Right-padded mixed lengths through ``FedPFTService``, one prompt per
    row (the step's ``(B,)`` layout): padding leaks neither through the
    conv nor through the state."""
    cfg, params = f32
    svc = _service(cfg, params, 4)
    svc._layout = serve.pad_batch
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in
               (70, 3, 33, 1, 64, 9)]
    calls, got = _extract(svc, prompts)
    assert calls == [((4, 128), (4,)), ((4, 64), (4,))]
    err = row_errors(got, REF.features(cfg, params, prompts))
    assert err.max() < TOL_F32, err


# Packed into two rows of 128 (first-fit decreasing): row 0 holds, left to
# right, 70, 3, 1, 9, 2 and 40 tokens, so segments shorter than the conv
# (4) follow others; row 1 holds 33 and 64.
PACKED = (70, 3, 33, 1, 64, 9, 2, 40)


def _packed_error(cfg, params):
    svc = _service(cfg, params, 8)
    assert svc._layout is serve.pack_batch
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in PACKED]
    calls, got = _extract(svc, prompts)
    assert calls == [((2, 128), (2, 8))]
    return row_errors(got, REF.features(cfg, params, prompts))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_service_packs_prompts_as_each_alone(f32, dtype):
    """Packed rows through ``FedPFTService``: each prompt's features are
    the plain reference's of that prompt alone — neither the conv history
    nor the SSD state crosses a segment start, nor does attention."""
    cfg, params = f32 if dtype == "float32" else (small(dtype),) * 2
    if dtype != "float32":
        params = make_params(cfg)
    err = _packed_error(cfg, params)
    assert err.max() < (TOL_F32 if dtype == "float32" else TOL_BF16), err


def _norm_before_gate(y, z, gn, eps):
    return (rms_norm(y.astype(jnp.float32), gn, eps)
            * jax.nn.silu(z.astype(jnp.float32))).astype(y.dtype)


def _state_dropped_at_chunks(real):
    def ssd(x, a_log, B, C, S0, chunk=256, segment_ids=None):
        ys = []
        for t0 in range(0, x.shape[2], chunk):
            y, S = real(x[:, :, t0:t0 + chunk], a_log[..., t0:t0 + chunk],
                        B[:, t0:t0 + chunk], C[:, t0:t0 + chunk],
                        S0 if t0 == 0 else jnp.zeros_like(S0), chunk,
                        None if segment_ids is None
                        else segment_ids[:, t0:t0 + chunk])
            ys.append(y)
        return jnp.concatenate(ys, axis=2), S
    return ssd


@pytest.mark.parametrize("fault", [
    "norm_before_gate", "dt_clamp_restored", "state_dropped_at_chunk",
    "d_skip_dropped", "rope_on_nope", "residual_multiplier_one"])
def test_a_planted_fault_fails_the_comparison(f32, monkeypatch, fault):
    cfg, params = f32
    prog_cfg, prog_params = cfg, params
    if fault == "norm_before_gate":
        monkeypatch.setattr(MM, "gated_norm", _norm_before_gate)
    elif fault == "dt_clamp_restored":
        real = MM.delta
        monkeypatch.setattr(MM, "delta", lambda dt_raw, dt_bias: jnp.clip(
            real(dt_raw, dt_bias), 1e-3, 1e-1))
    elif fault == "state_dropped_at_chunk":
        monkeypatch.setattr(MM, "ssd_chunked",
                            _state_dropped_at_chunks(MM.ssd_chunked))
    elif fault == "d_skip_dropped":
        prog_params = jax.tree_util.tree_map_with_path(
            lambda kp, a: jnp.zeros_like(a)
            if jax.tree_util.keystr(kp).endswith("['D']") else a, params)
    elif fault == "rope_on_nope":
        prog_cfg = dataclasses.replace(cfg, use_rope=True)
    elif fault == "residual_multiplier_one":
        prog_cfg = dataclasses.replace(cfg, residual_scale=1.0)
    err = hidden_error(prog_cfg, prog_params, cfg, params, 70)
    assert err > 10 * TOL_F32, (fault, err)


def _state_not_reset(real):
    def ssd(*args, segment_ids=None, **kw):
        return real(*args, **kw)
    return ssd


def _conv_history_leaks(real):
    def conv(padded, kern, T, positions=None):
        return real(padded, kern, T)
    return conv


@pytest.mark.parametrize("fault", ["state_not_reset", "conv_history_leaks"])
def test_a_planted_fault_fails_the_packed_comparison(f32, monkeypatch,
                                                     fault):
    cfg, params = f32
    if fault == "state_not_reset":
        monkeypatch.setattr(MM, "ssd_chunked", _state_not_reset(
            MM.ssd_chunked))
    else:
        monkeypatch.setattr(MM, "causal_conv", _conv_history_leaks(
            MM.causal_conv))
    err = _packed_error(cfg, params)
    assert err.max() > 10 * TOL_F32, (fault, err)


def _dense_features(name):
    cfg = get_config(name).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (16, 9, 3)]
    step = jax.jit(serve.make_feature_step(cfg))
    rows = step(params, *serve.pad_batch(prompts, 16, 3))
    packed = step(params, *serve.pack_batch(prompts, 16, 4))
    return np.asarray(rows, np.float32), np.asarray(packed, np.float32)


@pytest.mark.parametrize("name", ["granite-3-2b", "yi-34b"])
def test_dense_features_are_bit_identical_to_before(name):
    """The new config fields' defaults leave the dense program as it was:
    features of one-row and packed batches equal the recorded ones."""
    want = json.loads(GOLDEN.read_text())[name]
    rows, packed = _dense_features(name)
    np.testing.assert_array_equal(rows, np.asarray(want["rows"], np.float32))
    np.testing.assert_array_equal(packed,
                                  np.asarray(want["packed"], np.float32))


def _jaxpr_digest(fn, *args):
    return hashlib.sha256(str(jax.make_jaxpr(fn)(*args)).encode()).hexdigest()


def _unpacked_digest(name):
    sds = jax.ShapeDtypeStruct
    if name == "ssd_chunked":
        f32 = jnp.float32
        return _jaxpr_digest(
            lambda x, a, B, C, S: MM.ssd_chunked(x, a, B, C, S, chunk=16),
            sds((2, 3, 40, 4), f32), sds((2, 3, 40), f32),
            sds((2, 40, 5), f32), sds((2, 40, 5), f32),
            sds((2, 3, 5, 4), f32))
    arch, call = name.split(".")
    cfg = (small("bfloat16") if arch == "granite-4-h-micro"
           else get_config(arch).reduced())
    p = jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.PRNGKey(0))
    if call == "final_hidden":
        return _jaxpr_digest(
            lambda p, t: M.final_hidden(cfg, p, {"tokens": t}), p,
            sds((2, 40), jnp.int32))
    rows = 2 if call == "feature_step_packed" else 4
    length = (rows, 4) if call == "feature_step_packed" else (4,)
    return _jaxpr_digest(serve.make_feature_step(cfg), p,
                         sds((rows, 16), jnp.int32), sds(length, jnp.int32))


@pytest.mark.parametrize("name", [
    "ssd_chunked", "granite-4-h-micro.final_hidden",
    "granite-4-h-micro.feature_step", "zamba2-7b.final_hidden",
    "zamba2-7b.feature_step", "granite-3-2b.final_hidden",
    "granite-3-2b.feature_step", "granite-3-2b.feature_step_packed"])
def test_unpacked_programs_trace_as_before(name):
    """Segment isolation in the Mamba-2 mixer adds nothing to a call
    without segment ids: the SSD, both hybrids' unpacked feature steps and
    the dense steps trace the jaxprs recorded before it existed."""
    want = json.loads(JAXPRS.read_text())[name]
    assert _unpacked_digest(name) == want
