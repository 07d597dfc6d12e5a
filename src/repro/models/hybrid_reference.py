"""Plain float32 reference of the granite-4.0-h layer stack (the ``hybrid``
family with a ``layer_pattern``), against which the tests hold the program.

Written from the published description — Hugging Face's
``GraniteMoeHybridDecoderLayer`` with no experts and its
``GraniteMoeHybridMambaLayer`` — in straightforward ``jax.numpy``: every
product in float32 at HIGHEST precision, one layer after another, no chunked
scan, no cache and no padding.  A layer is

    h = x + r · mixer(norm₁(x)),   out = h + r · mlp(norm₂(h))

with r the residual multiplier and the mixer, by layer index, either NoPE
grouped-query attention (softmax scale from the config) or Mamba-2:
in_proj → [z, xBC, dt]; causal depthwise conv over xBC with bias, SiLU;
Δ = softplus(dt + dt_bias), A = −exp(A_log); the SSD as its sequential
recurrence

    S_t = exp(Δ_t A) S_{t−1} + Δ_t B_t x_tᵀ,   y_t = C_tᵀ S_t + D x_t;

y ← RMSNorm(y · silu(z)) over d_inner (one group); out_proj.  The
embedding is scaled by the embedding multiplier, and every RMSNorm takes
the config's ε.  It reads the program's parameter pytree
(``model.init_params``'s layout), upcast to float32.

Departures from the published model:
- ``lm_head`` (tied to the embedding) is not applied: the result is the
  final normed hidden state, which feature extraction pools.
- The weights are whatever the caller passes: seeded random in the tests.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _mm(x, w):
    return jnp.einsum("...i,io->...o", x, w, precision=HI)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def attention(cfg, x, w):
    """Causal grouped-query attention with no position embedding."""
    if cfg.use_rope:
        raise ValueError("hybrid_reference: NoPE attention only")
    B, S, _ = x.shape
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _mm(x, w["wq"]).reshape(B, S, h, dh)
    k = jnp.repeat(_mm(x, w["wk"]).reshape(B, S, hk, dh), h // hk, axis=2)
    v = jnp.repeat(_mm(x, w["wv"]).reshape(B, S, hk, dh), h // hk, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * cfg.attn_scale
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)
    return _mm(o.reshape(B, S, h * dh), w["wo"])


def mlp(x, w):
    return _mm(jax.nn.silu(_mm(x, w["w_gate"])) * _mm(x, w["w_in"]),
               w["w_out"])


def causal_conv(x, kern, bias):
    """Depthwise causal conv: out_t = Σ_i kern_i · x_{t−K+1+i} + bias."""
    K, T = kern.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(padded[:, i:i + T] * kern[i] for i in range(K)) + bias


def ssd_sequential(x, dt, A, B, C, D):
    """x: (Bt, T, H, P); dt: (Bt, T, H); A, D: (H,); B, C: (Bt, T, N).
    The recurrence one step at a time, from a zero state."""
    Bt, T, H, P = x.shape
    N = B.shape[-1]

    def step(S, inp):                                 # S: (Bt, H, N, P)
        x_t, dt_t, B_t, C_t = inp
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None, None] * B_t[:, None, :, None])
             * x_t[:, :, None, :])
        return S, jnp.einsum("bn,bhnp->bhp", C_t, S, precision=HI)

    _, ys = jax.lax.scan(step, jnp.zeros((Bt, H, N, P), jnp.float32),
                         (x.swapaxes(0, 1), dt.swapaxes(0, 1),
                          B.swapaxes(0, 1), C.swapaxes(0, 1)))
    return ys.swapaxes(0, 1) + D[:, None] * x


def mamba_mixer(cfg, x, w):
    Bt, T, _ = x.shape
    d_inner = cfg.ssm_expand * cfg.d_model
    P, N = cfg.ssm_head_dim, cfg.ssm_state
    H = d_inner // P
    z, xbc, dt = jnp.split(_mm(x, w["w_in"]),
                           [d_inner, 2 * d_inner + 2 * N], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, w["conv_w"], w["conv_b"]))
    xs, B, C = jnp.split(xbc, [d_inner, d_inner + N], axis=-1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = ssd_sequential(xs.reshape(Bt, T, H, P), dt, -jnp.exp(w["A_log"]),
                       B, C, w["D"]).reshape(Bt, T, d_inner)
    y = rms_norm(y * jax.nn.silu(z), w["gn"], cfg.norm_eps)
    return _mm(y, w["w_out"])


@partial(jax.jit, static_argnums=0)
def hidden(cfg, params, tokens):
    """(B, S) tokens → (B, S, d) final normed hidden states, float32."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    eps, r = cfg.norm_eps, cfg.residual_scale
    x = p["embed"][tokens] * cfg.embed_scale
    seen = {"m": 0, "a": 0}
    for layer in range(cfg.n_layers):
        kind = cfg.layer_pattern[layer % len(cfg.layer_pattern)]
        stack = p["blocks"]["mamba" if kind == "m" else "attn"]
        w = jax.tree.map(lambda a: a[seen[kind]], stack)
        seen[kind] += 1
        xn = rms_norm(x, w["ln1"], eps)
        y = (mamba_mixer(cfg, xn, w["mixer"]) if kind == "m"
             else attention(cfg, xn, w))
        x = x + r * y
        x = x + r * mlp(rms_norm(x, w["ln2"], eps), w)
    return rms_norm(x, p["final_norm"], eps)


def features(cfg, params, prompts):
    """Each prompt alone, unpadded: the mean of its final hidden states.
    Returns (len(prompts), d) float32."""
    return jnp.stack([jnp.mean(hidden(cfg, params, jnp.asarray(t)[None])[0],
                               axis=0) for t in prompts])
