"""Plain float32 references, independent of the program under test.

``decoder_features`` is the dense decoder's mean-pooled final hidden state
written from the equations in straightforward ``jax.numpy``: pre-norm
blocks of RMSNorm, grouped-query attention with rotary positions (the
rotate-half form), a SwiGLU MLP, a final RMSNorm, and the mean over each
row's real positions.  It makes its weights again from the seed, one layer
at a time (``weights.layer_f32``, cast to the served type and back), so it
holds one layer beside the activations and nothing the program made.
Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product is otherwise one bfloat16 pass.

``quant="fp8"`` is the control: the same computation with both operands of
every projection rounded to float8 e4m3 under an absmax scale (per matrix
for weights, per row for activations), the precision below the served
bfloat16 that a later change could be tempted to take.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import weights as W

HI = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def fp8(x, axis):
    """Round to e4m3 under an absmax scale taken over ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(FP8).astype(jnp.float32) * s


def _mm(x, w, quant):
    if quant == "fp8":
        x, w = fp8(x, -1), fp8(w, None)
    return jnp.einsum("...i,io->...o", x, w, precision=HI)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x: (B, S, H, D) at positions 0..S-1; rotates the two halves."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(x, w, s, theta, eps, quant=None):
    """One pre-norm decoder layer in float32.  x: (B, S, d)."""
    B, S, _ = x.shape
    h, hk, dh = s["h"], s["hk"], s["dh"]
    xn = rms_norm(x, w["ln1"], eps)
    q = rope(_mm(xn, w["wq"], quant).reshape(B, S, h, dh), theta)
    k = rope(_mm(xn, w["wk"], quant).reshape(B, S, hk, dh), theta)
    v = _mm(xn, w["wv"], quant).reshape(B, S, hk, dh)
    q = q.reshape(B, S, hk, h // hk, dh)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", q, k, precision=HI) / dh ** 0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=HI)
    x = x + _mm(o.reshape(B, S, h * dh), w["wo"], quant)
    xn = rms_norm(x, w["ln2"], eps)
    y = jax.nn.silu(_mm(xn, w["w_gate"], quant)) * _mm(xn, w["w_in"], quant)
    return x + _mm(y, w["w_out"], quant)


@partial(jax.jit, static_argnames=("s", "theta", "eps", "quant", "dtype"))
def _layer(key, layer, x, *, s, theta, eps, quant, dtype):
    sd = dict(s)
    w = {k: W.served(v, dtype) for k, v in
         W.layer_f32(W.layer_key(key, layer), sd).items()}
    return block(x, w, sd, theta, eps, quant)


@partial(jax.jit, static_argnames=("s", "dtype"))
def _embed(key, tokens, *, s, dtype):
    return W.served(W.embed_f32(key, dict(s)), dtype)[tokens]


@partial(jax.jit, static_argnames=("s", "eps", "dtype"))
def _pool(key, x, lengths, *, s, eps, dtype):
    x = rms_norm(x, W.served(W.final_norm_f32(key, dict(s)), dtype), eps)
    mask = (jnp.arange(x.shape[1])[None, :] < lengths[:, None])[..., None]
    return jnp.sum(jnp.where(mask, x, 0.0), 1) / lengths[:, None]


def decoder_features(seed: int, model: dict, tokens: np.ndarray,
                     lengths: np.ndarray, quant=None) -> np.ndarray:
    """(B, S) right-padded tokens with real ``lengths`` → (B, d) float32."""
    out = features(seed, model, jnp.asarray(tokens),
                   jnp.asarray(lengths, jnp.float32), quant)
    return np.asarray(jax.device_get(out), np.float32)


def features(seed: int, model: dict, tokens, lengths, quant=None):
    """``decoder_features`` on arrays (also inside a traced function)."""
    s = W.dims(model)
    frozen = tuple(sorted(s.items()))
    run = model["as_run"]
    theta, eps = float(run["rope_theta"]), float(run["rms_norm_eps"])
    dtype = jnp.dtype(model["torch_dtype"])
    key = W.root_key(seed)
    x = _embed(key, tokens, s=frozen, dtype=dtype)
    for layer in range(s["L"]):
        x = _layer(key, jnp.int32(layer), x, s=frozen, theta=theta, eps=eps,
                   quant=quant, dtype=dtype)
    return _pool(key, x, lengths, s=frozen, eps=eps, dtype=dtype)


def row_errors(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Relative L2 error of each row against the reference row."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return (np.linalg.norm(got - want, axis=-1)
            / np.maximum(np.linalg.norm(want, axis=-1), 1e-30))
