"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
plus hypothesis property tests (per the kernel contract in DESIGN.md §8)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.core import gmm as G
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gmm_estep import estep, estep_fused


def _estep_inputs(key, N, K, d, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (N, d), dtype)
    mu = jax.random.normal(ks[1], (K, d), dtype)
    var = jax.nn.softplus(jax.random.normal(ks[2], (K, d))) + 0.1
    pi = jax.nn.softmax(jax.random.normal(ks[3], (K,)))
    return x, mu, var.astype(dtype), pi


class TestGmmEstepKernel:
    @pytest.mark.parametrize("N,K,d", [
        (32, 1, 4), (100, 3, 8), (257, 10, 64), (512, 50, 512),
        (33, 7, 17), (128, 128, 128), (1000, 5, 300),
    ])
    def test_shape_sweep(self, key, N, K, d):
        x, mu, var, pi = _estep_inputs(key, N, K, d)
        out = estep(x, mu, var, pi)
        exp = ref.estep_ref(x, mu, var, pi)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=3e-4, atol=3e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtype_sweep(self, key, dtype):
        x, mu, var, pi = _estep_inputs(key, 64, 4, 32, dtype)
        out = estep(x, mu, var, pi)
        exp = ref.estep_ref(x, mu, var, pi)
        tol = 3e-4 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32),
                                   rtol=tol, atol=tol)

    def test_spherical_broadcast(self, key):
        x, mu, _, pi = _estep_inputs(key, 50, 3, 16)
        var_s = jnp.asarray([0.5, 1.0, 2.0])
        out = estep(x, mu, jnp.broadcast_to(var_s[:, None], (3, 16)), pi)
        exp = ref.estep_ref(x, mu, jnp.broadcast_to(var_s[:, None], (3, 16)),
                            pi)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=3e-4, atol=3e-4)

    def test_block_shapes(self, key):
        x, mu, var, pi = _estep_inputs(key, 300, 40, 96)
        exp = ref.estep_ref(x, mu, var, pi)
        for bn, bk in [(64, 16), (128, 128), (256, 8)]:
            out = estep(x, mu, var, pi, block_n=bn, block_k=bk)
            np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                       rtol=3e-4, atol=3e-4)

    def test_spher_genuine_1d_var(self, key):
        """Regression: a REAL (K,) spher variance used to raise ValueError
        (broadcast_to((K,) → (K,d))) in both the kernel and the fallback —
        the old test pre-broadcast to (K, d) and never caught it."""
        x, mu, _, pi = _estep_inputs(key, 50, 3, 16)
        var_s = jnp.asarray([0.5, 1.0, 2.0])                  # (K,)
        exp = ref.estep_ref(x, mu,
                            jnp.broadcast_to(var_s[:, None], (3, 16)), pi)
        out = estep(x, mu, var_s, pi)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=3e-4, atol=3e-4)
        for use in (False, True):
            ops.use_pallas(use)
            got = ops.gmm_estep(x, mu, var_s, pi)
            np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                       rtol=3e-4, atol=3e-4)


class TestGmmEstepFused:
    """The fused two-output contract: numerators + row logsumexp from one
    tiled pass, batched over a stack of fits (DESIGN.md §8)."""

    @pytest.mark.parametrize("N,K,d", [
        (32, 1, 4), (100, 3, 8), (257, 10, 64), (33, 7, 17), (300, 40, 96),
    ])
    def test_matches_oracle_unbatched(self, key, N, K, d):
        x, mu, var, pi = _estep_inputs(key, N, K, d)
        lp, lse = estep_fused(x, mu, var, pi)
        lp_exp, lse_exp = ref.estep_fused_ref(x, mu, var, pi)
        assert lp.shape == (N, K) and lse.shape == (N,)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_exp),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_exp),
                                   rtol=3e-4, atol=3e-4)

    @pytest.mark.parametrize("Bx,r", [(1, 1), (2, 1), (2, 3), (1, 4)])
    def test_batched_shared_x(self, key, Bx, r):
        """B = Bx·r fits, each group of r sharing one feature block — the
        (clients × classes) layout of fit_classwise_gmms_batched."""
        B, N, K, d = Bx * r, 45, 5, 24
        ks = jax.random.split(key, 4)
        x = jax.random.normal(ks[0], (Bx, N, d))
        mu = jax.random.normal(ks[1], (B, K, d))
        var = jax.nn.softplus(jax.random.normal(ks[2], (B, K, d))) + 0.1
        pi = jax.nn.softmax(jax.random.normal(ks[3], (B, K)))
        lp, lse = estep_fused(x, mu, var, pi)
        lp_exp, lse_exp = ref.estep_fused_ref(x, mu, var, pi)
        assert lp.shape == (B, N, K) and lse.shape == (B, N)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_exp),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_exp),
                                   rtol=3e-4, atol=3e-4)

    def test_2d_x_with_batched_params(self, key):
        """One unbatched (N, d) feature block against a batched (B, K, d)
        parameter stack — the Bx = 1 shared-x case without the explicit
        leading axis."""
        ks = jax.random.split(key, 4)
        x = jax.random.normal(ks[0], (30, 8))
        mu = jax.random.normal(ks[1], (4, 3, 8))
        var = jax.nn.softplus(jax.random.normal(ks[2], (4, 3, 8))) + 0.1
        pi = jax.nn.softmax(jax.random.normal(ks[3], (4, 3)))
        lp, lse = estep_fused(x, mu, var, pi)
        lp_exp, lse_exp = ref.estep_fused_ref(x, mu, var, pi)
        assert lp.shape == (4, 30, 3) and lse.shape == (4, 30)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_exp),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_exp),
                                   rtol=3e-4, atol=3e-4)

    def test_batched_spher_var(self, key):
        ks = jax.random.split(key, 4)
        x = jax.random.normal(ks[0], (3, 30, 12))
        mu = jax.random.normal(ks[1], (3, 4, 12))
        var = jax.nn.softplus(jax.random.normal(ks[2], (3, 4))) + 0.1
        pi = jax.nn.softmax(jax.random.normal(ks[3], (3, 4)))
        lp, lse = estep_fused(x, mu, var, pi)
        lp_exp, lse_exp = ref.estep_fused_ref(x, mu, var, pi)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_exp),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_exp),
                                   rtol=3e-4, atol=3e-4)

    def test_block_shapes(self, key):
        """Online-logsumexp must agree across K-block partitionings."""
        x, mu, var, pi = _estep_inputs(key, 300, 40, 96)
        _, lse_exp = ref.estep_fused_ref(x, mu, var, pi)
        for bn, bk in [(64, 16), (128, 128), (256, 8)]:
            _, lse = estep_fused(x, mu, var, pi, block_n=bn, block_k=bk)
            np.testing.assert_allclose(np.asarray(lse),
                                       np.asarray(lse_exp),
                                       rtol=3e-4, atol=3e-4)

    def test_ops_dispatch(self, key):
        x, mu, var, pi = _estep_inputs(key, 40, 3, 8)
        ops.use_pallas(False)
        a_lp, a_lse = ops.gmm_estep_fused(x, mu, var, pi)
        ops.use_pallas(True)
        b_lp, b_lse = ops.gmm_estep_fused(x, mu, var, pi)
        np.testing.assert_allclose(np.asarray(a_lp), np.asarray(b_lp),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(a_lse), np.asarray(b_lse),
                                   rtol=3e-4, atol=3e-4)


class TestFitGmmBackendParity:
    """fit_gmm / fit_classwise_gmms E-step goes through ops.gmm_estep_fused:
    Pallas (interpret) and XLA-reference backends must produce the same
    fits for every covariance family — including a genuine (K,) spher
    cov — since the kernel IS the EM hot path now."""

    @pytest.mark.parametrize("cov", ["full", "diag", "spher"])
    def test_fit_gmm_parity(self, key, cov):
        x = jax.random.normal(key, (120, 10))
        w = jnp.ones(120)
        cfg = G.GMMConfig(n_components=3, cov_type=cov, n_iter=8)
        ops.use_pallas(False)
        ga, lla = G.fit_gmm(key, x, w, cfg)
        ops.use_pallas(True)
        gb, llb = G.fit_gmm(key, x, w, cfg)
        if cov == "spher":
            assert ga["cov"].shape == gb["cov"].shape == (3,)
        for f in ("pi", "mu", "cov"):
            np.testing.assert_allclose(np.asarray(ga[f]),
                                       np.asarray(gb[f]),
                                       rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(float(lla), float(llb),
                                   rtol=1e-3, atol=1e-3)

    def test_fit_classwise_parity(self, key):
        labels = jax.random.randint(key, (90,), 0, 3)
        x = jax.random.normal(key, (90, 6)) \
            + 3.0 * jax.nn.one_hot(labels, 3) @ jnp.ones((3, 6))
        cfg = G.GMMConfig(n_components=2, cov_type="spher", n_iter=6)
        ops.use_pallas(False)
        ga, ca, _ = G.fit_classwise_gmms(key, x, labels, 3, cfg)
        ops.use_pallas(True)
        gb, cb, _ = G.fit_classwise_gmms(key, x, labels, 3, cfg)
        np.testing.assert_array_equal(np.asarray(ca), np.asarray(cb))
        np.testing.assert_allclose(np.asarray(ga["mu"]),
                                   np.asarray(gb["mu"]),
                                   rtol=2e-3, atol=2e-3)


class TestFlashAttentionKernel:
    CASES = [
        # B, H, Hkv, Sq, Sk, D, causal, window, prefix
        (1, 4, 4, 64, 64, 32, True, 0, 0),
        (2, 8, 2, 128, 128, 64, True, 0, 0),      # GQA
        (1, 4, 2, 100, 100, 32, True, 0, 0),      # ragged
        (1, 2, 2, 256, 256, 32, True, 64, 0),     # sliding window
        (1, 4, 1, 64, 256, 32, True, 0, 0),       # MQA, continued prefill
        (1, 2, 2, 96, 96, 32, False, 0, 0),       # bidirectional (encoder)
        (1, 4, 4, 128, 128, 32, True, 0, 16),     # VLM image prefix
        (2, 4, 2, 1, 192, 64, True, 0, 0),        # decode: 1 query
        (1, 2, 2, 128, 128, 16, True, 32, 8),     # window + prefix
    ]

    @pytest.mark.parametrize("B,H,Hkv,Sq,Sk,D,causal,window,prefix", CASES)
    def test_matches_oracle(self, key, B, H, Hkv, Sq, Sk, D, causal,
                            window, prefix):
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, H, Sq, D))
        k = jax.random.normal(ks[1], (B, Hkv, Sk, D))
        v = jax.random.normal(ks[2], (B, Hkv, Sk, D))
        out = flash_attention(q, k, v, causal=causal, window=window,
                              prefix=prefix)
        exp = ref.attention_ref(q, k, v, causal=causal, window=window,
                                prefix=prefix)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=2e-3, atol=2e-3)

    def test_bf16(self, key):
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (1, 2, 64, 32), jnp.bfloat16)
        k = jax.random.normal(ks[1], (1, 2, 64, 32), jnp.bfloat16)
        v = jax.random.normal(ks[2], (1, 2, 64, 32), jnp.bfloat16)
        out = flash_attention(q, k, v)
        exp = ref.attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32),
                                   rtol=5e-2, atol=5e-2)

    def test_block_shapes(self, key):
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (1, 2, 160, 32))
        k = jax.random.normal(ks[1], (1, 2, 160, 32))
        v = jax.random.normal(ks[2], (1, 2, 160, 32))
        exp = ref.attention_ref(q, k, v)
        for bq, bk in [(32, 32), (64, 128), (160, 40)]:
            out = flash_attention(q, k, v, block_q=bq, block_k=bk)
            np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                       rtol=2e-3, atol=2e-3)


@pytest.mark.slow
@settings(max_examples=12, deadline=None)
@given(N=st.integers(4, 150), K=st.integers(1, 20), d=st.integers(1, 64))
def test_estep_property(N, K, d):
    """Property: kernel == oracle for arbitrary shapes, and responsibilities
    normalize (logsumexp over K of (logp − log π) ≥ per-component logp)."""
    key = jax.random.PRNGKey(N * 1001 + K * 31 + d)
    x, mu, var, pi = _estep_inputs(key, N, K, d)
    out = np.asarray(estep(x, mu, var, pi))
    exp = np.asarray(ref.estep_ref(x, mu, var, pi))
    np.testing.assert_allclose(out, exp, rtol=5e-4, atol=5e-4)


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(Sq=st.integers(1, 96), extra=st.integers(0, 64),
       H=st.sampled_from([1, 2, 4]), G=st.sampled_from([1, 2]),
       window=st.sampled_from([0, 16]))
def test_flash_property(Sq, extra, H, G, window):
    """Property: online-softmax output == dense-softmax oracle, any Sq/Sk,
    GQA grouping, optional window. Rows are convex combinations of V."""
    if H % G:
        return
    Sk = Sq + extra
    key = jax.random.PRNGKey(Sq * 7 + extra * 3 + H + window)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, H, Sq, 16))
    k = jax.random.normal(ks[1], (1, H // G, Sk, 16))
    v = jax.random.normal(ks[2], (1, H // G, Sk, 16))
    out = np.asarray(flash_attention(q, k, v, window=window))
    exp = np.asarray(ref.attention_ref(q, k, v, window=window))
    np.testing.assert_allclose(out, exp, rtol=3e-3, atol=3e-3)
    assert np.abs(out).max() <= np.abs(np.asarray(v)).max() + 1e-3


def test_ops_dispatch(key):
    """ops.use_pallas flips backends; results agree, and the platform
    picks the Pallas path (interpret mode on the CPU) by default."""
    x, mu, var, pi = _estep_inputs(key, 40, 3, 8)
    ops.use_pallas(False)
    a = ops.gmm_estep(x, mu, var, pi)
    ops.use_pallas(True)
    b = ops.gmm_estep(x, mu, var, pi)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=3e-4, atol=3e-4)
    assert ops.backend() == (ops.PALLAS if jax.default_backend() == "tpu"
                             else ops.INTERPRET)
