"""Pallas TPU kernel for the GMM E-step hot path (diag/spher families).

The per-fit workload is an (N, K) log-responsibility matrix over d-dim
features. Expanding the Mahalanobis term makes it two GEMMs —

    maha[n,k] = x²_n · inv_k  −  2 x_n · (μ_k ⊙ inv_k)  +  c_k

— which maps directly onto the MXU. The kernel tiles N×K into 128-aligned
VMEM blocks; the d (contraction) axis stays whole per block (d ≤ ~8k keeps
an (BN, d) f32 x-tile well under VMEM).

Two entry points share the kernel body:

``estep``        one (N, K) problem, log-numerators only — the original
                 contract (``ref.estep_ref``).
``estep_fused``  the EM production path: a *batch* of B fits in one
                 ``pallas_call``, emitting the (B, N, K) log-numerators AND
                 the per-row logsumexp (B, N) from the same tiled pass —
                 responsibilities and ``L_EM`` never re-materialize the
                 (N, K) matrix in XLA. The logsumexp accumulates
                 flash-attention-style: running (m, l) statistics live in
                 VMEM scratch across the K-block sweep (the grid's minor
                 axis) and are finalized on the last K block.  It leaves
                 the kernel as a (B, N, 1) column, stored in (1, BN, 1)
                 blocks: a (1, BN) row block of a (B, N) array has a
                 second-to-last dim of 1 that is neither a multiple of 8
                 nor the whole axis once B > 1, which Mosaic refuses.

Both matmuls ask for ``Precision.HIGHEST`` (fp32 contraction on the MXU):
the Mahalanobis expansion subtracts two d-term sums of similar size, and
a bf16 pass would leave errors of several nats at d in the thousands.

Batching: grid = (B, N/BN, K/BK). Component parameters vary per fit, but the
feature rows are usually *shared* by groups of fits (one client's features,
C per-class weighted fits — ``fit_classwise_gmms``): x may be passed as
(Bx, N, d) with B = Bx·r and the index map streams block (b // r, i) — no
materialized repeat, mirroring the GQA trick in ``flash_attention``.

Variance accepts diag ``(…, K, d)`` or spher ``(…, K)`` — spher expands via
``var[..., None]`` *here* (a genuine (K,) input used to crash both this
kernel and the XLA fallback; see tests/test_kernels.py regression).

Full covariance is intentionally NOT a kernel: its E-step is
Cholesky/triangular-solve dominated (not MXU-shaped) and is left to XLA —
see DESIGN.md §8.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LOG2PI = math.log(2.0 * math.pi)
NEG_INF = -1e30


def _logp_block(x_ref, xsq_ref, inv_ref, muinv_ref, const_ref):
    """One (BN, BK) tile of log-numerators: two MXU matmuls + broadcast add."""
    x = x_ref[0]                         # (BN, d) f32
    xsq = xsq_ref[0]                     # (BN, d) f32
    inv = inv_ref[0]                     # (BK, d) f32
    muinv = muinv_ref[0]                 # (BK, d) f32
    const = const_ref[0]                 # (1, BK) f32
    dims = (((1,), (1,)), ((), ()))
    hi = jax.lax.Precision.HIGHEST
    maha = (
        jax.lax.dot_general(xsq, inv, dims, precision=hi,
                            preferred_element_type=jnp.float32)
        - 2.0 * jax.lax.dot_general(x, muinv, dims, precision=hi,
                                    preferred_element_type=jnp.float32)
    )
    return -0.5 * maha + const


def _estep_kernel(x_ref, xsq_ref, inv_ref, muinv_ref, const_ref, out_ref):
    out_ref[0] = _logp_block(x_ref, xsq_ref, inv_ref, muinv_ref, const_ref)


def _estep_fused_kernel(x_ref, xsq_ref, inv_ref, muinv_ref, const_ref,
                        out_ref, lse_ref, m_scr, l_scr):
    """Numerator tile + online-logsumexp update across the K sweep.

    Padded K columns carry const = NEG_INF, so their exp underflows to 0
    against any real row max (every K block contains ≥ 1 real column —
    padding is always < BK)."""
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    logp = _logp_block(x_ref, xsq_ref, inv_ref, muinv_ref, const_ref)
    out_ref[0] = logp

    m_prev = m_scr[...]                               # (BN, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logp, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logp - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        lse_ref[0] = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))


def _pad_to(a, axis, mult, value=0.0):
    size = a.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def _prep(x, mu, var, pi):
    """Normalize to batched f32: x (Bx,N,d); mu/var (B,K,d); pi (B,K).

    Accepts unbatched 2D inputs (promoted to B=1) and spher variance with
    one fewer trailing dim than mu."""
    batched = mu.ndim == 3
    if not batched:
        mu, var, pi = mu[None], var[None], pi[None]
    if x.ndim == 2:                      # one feature block, shared by all
        x = x[None]
    x = x.astype(jnp.float32)
    mu = mu.astype(jnp.float32)
    var = var.astype(jnp.float32)
    if var.ndim == mu.ndim - 1:          # spher: (B, K) → (B, K, d)
        var = var[..., None]
    var = jnp.broadcast_to(var, mu.shape)
    return batched, x, mu, var, pi.astype(jnp.float32)


def _estep_call(x, mu, var, pi, *, block_n, block_k, fused, interpret):
    """Shared pallas_call builder. x: (Bx, N, d); mu/var: (B, K, d)."""
    Bx, N, d = x.shape
    B, K = mu.shape[0], mu.shape[1]
    assert B % Bx == 0, \
        f"batch {B} must be a multiple of the {Bx} shared feature blocks"
    r = B // Bx                          # fits sharing one feature block

    inv = 1.0 / var
    muinv = mu * inv
    # fold every per-component scalar into one constant row:
    #   c_k = log π_k − ½(d·log2π + Σlogσ² + Σμ²/σ²)
    const = (jnp.log(jnp.clip(pi, 1e-20))
             - 0.5 * (d * _LOG2PI + jnp.sum(jnp.log(var), -1)
                      + jnp.sum(jnp.square(mu) * inv, -1)))  # (B, K)

    bn = min(block_n, max(8, N))
    bk = min(block_k, max(8, K))
    xp = _pad_to(x, 1, bn)
    xsq = jnp.square(xp)
    invp = _pad_to(inv, 1, bk, value=1.0)
    muinvp = _pad_to(muinv, 1, bk)
    # NEG_INF in padded columns keeps them out of the fused logsumexp
    constp = _pad_to(const[:, None, :], 2, bk, value=NEG_INF)
    Np, Kp = xp.shape[1], invp.shape[1]

    in_specs = [
        pl.BlockSpec((1, bn, d), lambda b, i, j, r=r: (b // r, i, 0)),  # x
        pl.BlockSpec((1, bn, d), lambda b, i, j, r=r: (b // r, i, 0)),  # x²
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),   # inv
        pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),   # μ·inv
        pl.BlockSpec((1, 1, bk), lambda b, i, j: (b, 0, j)),   # const
    ]
    out_spec = pl.BlockSpec((1, bn, bk), lambda b, i, j: (b, i, j))
    out_shape = jax.ShapeDtypeStruct((B, Np, Kp), jnp.float32)
    grid = (B, Np // bn, Kp // bk)       # K sweep is the minor axis

    if not fused:
        out = pl.pallas_call(
            _estep_kernel, grid=grid, in_specs=in_specs,
            out_specs=out_spec, out_shape=out_shape,
            interpret=interpret)(xp, xsq, invp, muinvp, constp)
        return out[:, :N, :K], None

    out, lse = pl.pallas_call(
        _estep_fused_kernel, grid=grid, in_specs=in_specs,
        out_specs=[out_spec,
                   pl.BlockSpec((1, bn, 1), lambda b, i, j: (b, i, 0))],
        out_shape=[out_shape,
                   jax.ShapeDtypeStruct((B, Np, 1), jnp.float32)],
        scratch_shapes=[
            # running (m, l) logsumexp stats — persist across the K sweep
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.float32),
        ],
        interpret=interpret)(xp, xsq, invp, muinvp, constp)
    return out[:, :N, :K], lse[:, :N, 0]


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def estep(x: jax.Array, mu: jax.Array, var: jax.Array, pi: jax.Array,
          *, block_n: int = 256, block_k: int = 128,
          interpret: bool = True) -> jax.Array:
    """log[π_k N(x_n | μ_k, diag Σ_k)] : (N, d) × (K, d) → (N, K).

    ``var`` may be diag ``(K, d)`` or spher ``(K,)``. Matches
    ``ref.estep_ref``. ``interpret=True`` runs the kernel body through
    the Pallas interpreter (CPU); on a TPU pass ``interpret=False``
    (``kernels.ops`` picks by platform).
    """
    assert mu.ndim == 2, \
        f"estep is single-fit (got mu {mu.shape}); use estep_fused"
    _, xb, mub, varb, pib = _prep(x, mu, var, pi)
    out, _ = _estep_call(xb, mub, varb, pib, block_n=block_n,
                         block_k=block_k, fused=False, interpret=interpret)
    return out[0]


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def estep_fused(x: jax.Array, mu: jax.Array, var: jax.Array, pi: jax.Array,
                *, block_n: int = 256, block_k: int = 128,
                interpret: bool = True):
    """Fused batched E-step: log-numerators AND their row logsumexp.

    x: (Bx, N, d) or (N, d); mu: (B, K, d) or (K, d) with B % Bx == 0 —
    each run of B//Bx consecutive fits shares one feature block (the
    classes axis of ``fit_classwise_gmms``). var: diag (…, K, d) or spher
    (…, K). Returns ``(logp, lse)`` with shapes ((B, N, K), (B, N)) — or
    ((N, K), (N,)) for unbatched inputs. Matches ``ref.estep_fused_ref``.
    """
    batched, xb, mub, varb, pib = _prep(x, mu, var, pi)
    out, lse = _estep_call(xb, mub, varb, pib, block_n=block_n,
                           block_k=block_k, fused=True, interpret=interpret)
    if not batched:
        return out[0], lse[0]
    return out, lse
