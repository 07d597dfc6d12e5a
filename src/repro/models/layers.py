"""Shared transformer building blocks (pure JAX, scan/shard_map friendly).

Conventions:
  * params are plain nested dicts of jnp arrays; scanned stacks carry a
    leading layer axis.
  * compute dtype is cfg.dtype (bf16 by default); norms/softmax/logits in f32.
  * attention supports GQA, causal/bidirectional, sliding window, and an
    incremental KV-cache (ring buffer when windowed).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig


# ---------------------------------------------------------------------------
# activation-sharding hook (§Perf): installed by the launch layer around
# lowering so GSPMD keeps tokens batch-sharded instead of replicating them.
# ---------------------------------------------------------------------------

_ACT_CONSTRAIN = None   # Optional[Callable[[Array, str], Array]]


class activation_sharding:
    """Context manager installing an activation sharding-constraint fn.

    ``fn(x, kind)`` with kind ∈ {"act", "logits"} returns x constrained."""

    def __init__(self, fn):
        self.fn = fn

    def __enter__(self):
        global _ACT_CONSTRAIN
        self._prev = _ACT_CONSTRAIN
        _ACT_CONSTRAIN = self.fn
        return self

    def __exit__(self, *exc):
        global _ACT_CONSTRAIN
        _ACT_CONSTRAIN = self._prev
        return False


def constrain(x, kind: str = "act"):
    if _ACT_CONSTRAIN is None:
        return x
    return _ACT_CONSTRAIN(x, kind)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = (1.0 / math.sqrt(fan_in)) if scale is None else scale
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def ones_init(_, shape, dtype):
    return jnp.ones(shape, dtype)


def zeros_init(_, shape, dtype):
    return jnp.zeros(shape, dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    freqs = rope_frequencies(x.shape[-1], theta)            # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                     # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, n_layers: int, dtype):
    """Stacked (L, ...) attention weights."""
    d, h, hk, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    L = (n_layers,)
    return {
        "wq": dense_init(ks[0], L + (d, h * dh), dtype),
        "wk": dense_init(ks[1], L + (d, hk * dh), dtype),
        "wv": dense_init(ks[2], L + (d, hk * dh), dtype),
        "wo": dense_init(ks[3], L + (h * dh, d), dtype),
    }


def _sdpa_chunked(q, k, v, *, causal: bool, window: int,
                  q_positions: jax.Array, chunk: int = 512,
                  kv_positions: Optional[jax.Array] = None,
                  kv_valid: Optional[jax.Array] = None,
                  segment_ids: Optional[jax.Array] = None,
                  scale: Optional[float] = None):
    """Chunked (over queries) scaled-dot-product attention.

    q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D). GQA via head grouping.
    q_positions: (Sq,) absolute positions of the queries.
    window > 0 restricts attention to the last `window` key positions.
    kv_positions: absolute position of each key slot (for ring buffers);
    kv_valid: bool mask of populated slots.
    segment_ids: (B, Sq) with Sq == Sk, for packed rows: a query attends
    only to keys of its own segment (on top of the causal/window mask).
    scale: the softmax scale, 1/√D unless given.
    """
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    q = q.reshape(B, Sq, Hkv, G, D)

    if kv_positions is None:
        kv_positions = jnp.arange(Sk)
    kv_positions = jnp.broadcast_to(kv_positions, (Sk,))

    def attend_block(q_blk, q_pos, q_seg=None):
        # q_blk: (B, C, Hkv, G, D); q_pos: (C,) absolute query positions;
        # q_seg: (B, C) segment ids of the queries (packed rows only)
        s = jnp.einsum("bchgd,bshd->bhgcs", q_blk.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        mask = jnp.ones((q_blk.shape[1], Sk), dtype=bool)
        rel = q_pos[:, None] - kv_positions[None, :]
        if causal:
            mask &= rel >= 0
        if window > 0:
            mask &= rel < window
        if kv_valid is not None:
            mask &= kv_valid[None, :]
        if q_seg is None:
            mask = mask[None, None, None]
        else:
            mask = (mask[None] & (q_seg[:, :, None]
                                  == segment_ids[:, None, :]))[:, None, None]
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgcs,bshd->bchgd", p, v.astype(jnp.float32))
        return o.astype(v.dtype)

    chunk = min(chunk, Sq)
    if Sq % chunk:
        chunk = Sq  # fall back to single block for ragged sizes
    n_chunks = Sq // chunk
    if n_chunks == 1:
        out = attend_block(q, q_positions, segment_ids)
    else:
        qs = q.reshape(B, n_chunks, chunk, Hkv, G, D).transpose(1, 0, 2, 3, 4, 5)
        pos = q_positions.reshape(n_chunks, chunk)
        blocks = (qs, pos)
        if segment_ids is not None:
            blocks += (segment_ids.reshape(B, n_chunks, chunk)
                       .transpose(1, 0, 2),)
        out = jax.lax.map(lambda args: attend_block(*args), blocks)
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sq, Hkv, G, D)
    return out.reshape(B, Sq, H, D)


@jax.named_scope("attention")
def attention(x, w, layer_cache, cfg: ModelConfig, *, positions,
              window: int = 0, use_cache: bool = False,
              segment_ids: Optional[jax.Array] = None):
    """Full attention layer: qkv proj + rope + sdpa + out proj.

    x: (B, S, d). positions: (S,) absolute positions of the input tokens.
    layer_cache: None or dict(k, v, pos) — updated functionally when
    use_cache. Returns (out, new_cache).

    segment_ids: (B, S) for rows that pack several sequences (no cache).
    Then ``positions`` is (B, S), each token's position within its own
    segment, which rope uses; causal order along the row is causal order
    within each segment, and the segment mask keeps the rest apart.

    ``cfg.use_rope`` False is NoPE: positions enter only through the
    causal mask.  ``cfg.attn_scale`` (when nonzero) is the softmax scale.
    """
    if use_cache and segment_ids is not None:
        raise ValueError("attention: packed segments take no KV cache")
    B, S, d = x.shape
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ w["wq"]).reshape(B, S, h, dh)
    k = (x @ w["wk"]).reshape(B, S, hk, dh)
    v = (x @ w["wv"]).reshape(B, S, hk, dh)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    sdpa = functools.partial(_sdpa_chunked, scale=cfg.attn_scale or None)
    if S > 1:
        # GQA K/V: when n_kv_heads doesn't divide the model axis, the TP
        # projection splits head_dim, which shards the QK contraction and
        # forces an all-reduce of the f32 (B,h,S,Sk) scores — the largest
        # collective in the baseline (§Perf iter 6, yi-34b prefill).
        # Gathering K/V to batch-only sharding is ~400× cheaper. Decode
        # (S==1) keeps the model-sharded cache: HBM capacity wins there.
        k = constrain(k)
        v = constrain(v)

    new_cache = layer_cache
    if use_cache:
        ck, cv = layer_cache["k"], layer_cache["v"]
        S_max = ck.shape[1]
        pos0 = positions[0]
        if window > 0 and S_max == window:
            # ---- ring buffer (cache depth == window) ----
            slot_ids = jnp.arange(S_max)
            if S == 1:
                # decode: write the one token, attend over the ring
                slots = positions % S_max
                ck = ck.at[:, slots].set(k)
                cv = cv.at[:, slots].set(v)
                latest_pos = positions[-1]
                kv_pos = latest_pos - ((latest_pos - slot_ids) % S_max)
                kv_valid = kv_pos >= 0
                new_cache = {"k": ck, "v": cv}
                out = sdpa(q, ck, cv, causal=cfg.causal,
                           window=window, q_positions=positions,
                           kv_positions=kv_pos, kv_valid=kv_valid)
            else:
                # prefill chunk: EVERY query must see its own window, so
                # attend over [old ring ∪ current chunk] — writing first
                # would evict keys that early queries still need.
                # Ring invariant: before this chunk it holds positions
                # pos0−W … pos0−1 (where ≥ 0).
                old_pos = pos0 - 1 - ((pos0 - 1 - slot_ids) % S_max)
                old_valid = (old_pos >= 0) & (pos0 > 0)
                kv_k = constrain(jnp.concatenate([ck, k], axis=1))
                kv_v = constrain(jnp.concatenate([cv, v], axis=1))
                kv_pos = jnp.concatenate([old_pos, positions])
                kv_valid = jnp.concatenate(
                    [old_valid, jnp.ones((S,), bool)])
                out = sdpa(q, kv_k, kv_v, causal=cfg.causal,
                           window=window, q_positions=positions,
                           kv_positions=kv_pos, kv_valid=kv_valid)
                slots = positions % S_max
                ck = ck.at[:, slots].set(k)   # duplicate slots: last wins
                cv = cv.at[:, slots].set(v)
                new_cache = {"k": ck, "v": cv}
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k, pos0, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v, pos0, axis=1)
            kv_pos = jnp.arange(S_max)
            kv_valid = kv_pos <= positions[-1]
            new_cache = {"k": ck, "v": cv}
            # prefill attends against a batch-only-sharded view of the
            # cache (§Perf iter 6); the returned cache keeps its decode
            # layout. S==1 decode attends the sharded cache directly.
            ak, av = (constrain(ck), constrain(cv)) if S > 1 else (ck, cv)
            out = sdpa(q, ak, av, causal=cfg.causal, window=window,
                       q_positions=positions, kv_positions=kv_pos,
                       kv_valid=kv_valid)
    elif segment_ids is None:
        out = sdpa(q, k, v, causal=cfg.causal, window=window,
                   q_positions=positions, kv_positions=positions)
    else:
        out = sdpa(q, k, v, causal=cfg.causal, window=window,
                   q_positions=jnp.arange(S),
                   segment_ids=segment_ids)
    return out.reshape(B, S, h * dh) @ w["wo"], new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, n_layers: int, dtype, d_ff=None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    L = (n_layers,)
    ks = jax.random.split(key, 3)
    w = {
        "w_in": dense_init(ks[0], L + (d, ff), dtype),
        "w_out": dense_init(ks[1], L + (ff, d), dtype),
    }
    if cfg.mlp_variant == "swiglu":
        w["w_gate"] = dense_init(ks[2], L + (d, ff), dtype)
    return w


@jax.named_scope("mlp")
def mlp(x, w, cfg: ModelConfig):
    h = x @ w["w_in"]
    if cfg.mlp_variant == "swiglu":
        h = jax.nn.silu(x @ w["w_gate"]) * h
    elif cfg.mlp_variant == "relu2":
        h = jnp.square(jax.nn.relu(h))
    elif cfg.mlp_variant == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(cfg.mlp_variant)
    return h @ w["w_out"]


# ---------------------------------------------------------------------------
# MoE (grouped capacity dispatch, Switch/Mesh-TF style)
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig, n_layers: int, dtype):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    L = (n_layers,)
    ks = jax.random.split(key, 4)
    w = {
        "router": dense_init(ks[0], L + (d, E), jnp.float32),
        "we_in": dense_init(ks[1], L + (E, d, ff), dtype),
        "we_out": dense_init(ks[2], L + (E, ff, d), dtype),
    }
    if cfg.mlp_variant == "swiglu":
        w["we_gate"] = dense_init(ks[3], L + (E, d, ff), dtype)
    return w


def moe(x, w, cfg: ModelConfig, group_size: int = 1024):
    """Mixture-of-experts with BATCHED per-group capacity dispatch.

    x: (B, S, d) -> (B, S, d), plus scalar aux load-balancing loss.

    §Perf iters 2-5 (see EXPERIMENTS.md): the group axis is a real tensor
    dimension sharded over the "data" mesh axis — NOT a ``lax.map``. A
    sequential map cannot be trip-parallelized by GSPMD, so every chip
    would step all global groups and re-read the expert weights each
    iteration. Batched dispatch reads the weights once per layer, and all
    contractions are explicit batched matmuls (einsums with one
    contraction dim) so nothing materializes an (g, E, cap, d) outer
    product. Position assignment uses ``lax.associative_scan`` (log-depth
    prefix sum — ``jnp.cumsum`` lowers to a quadratic reduce-window on
    some backends).
    """
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    # keep tokens sharded through the (B,S,d)→(T,d) reshape: without this
    # GSPMD replicates the token axis inside the dispatch (§Perf iter 3)
    xt = constrain(x.reshape(T, d))
    g = min(group_size, T)
    if T % g:
        g = T
    n = T // g
    cap = max(K, int(math.ceil(g * K / E * cfg.capacity_factor)))

    logits = (xt.astype(jnp.float32) @ w["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
    top_w, top_idx = jax.lax.top_k(probs, K)                 # (T, K)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # aux loss (load balance, computed globally)
    frac_tokens = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_idx, E, dtype=jnp.float32), axis=1),
        axis=0) / K
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs) * cfg.router_aux_coef

    xg = constrain(xt.reshape(n, g, d))                      # (n, g, d)
    idx = top_idx.reshape(n, g, K)
    tw = top_w.reshape(n, g, K)

    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)       # (n, g, K, E)
    flat = onehot.reshape(n, g * K, E)
    pos = (jax.lax.associative_scan(jnp.add, flat, axis=1)
           - flat).reshape(n, g, K, E)
    pos = jnp.sum(pos * onehot, axis=-1)                     # (n, g, K)
    keep = pos < cap
    pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32) * keep[..., None]
    disp = jnp.einsum("ngke,ngkc->ngec", onehot, pos_oh)     # (n, g, E, cap)
    comb = jnp.einsum("ngke,ngkc,ngk->ngec", onehot, pos_oh, tw)

    # gather tokens into expert slots: batched dot contracting g
    disp_m = disp.reshape(n, g, E * cap)
    xe = jnp.einsum("ngm,ngd->nmd", disp_m, xg.astype(jnp.float32))
    xe = xe.reshape(n, E, cap, d).astype(x.dtype)            # (n, E, cap, d)

    h = jnp.einsum("necd,edf->necf", xe, w["we_in"])
    if cfg.mlp_variant == "swiglu":
        h = jax.nn.silu(jnp.einsum("necd,edf->necf", xe, w["we_gate"])) * h
    elif cfg.mlp_variant == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    ye = jnp.einsum("necf,efd->necd", h, w["we_out"])        # (n, E, cap, d)

    # scatter back: batched dot contracting the E·cap slot axis
    comb_m = comb.reshape(n, g, E * cap)
    y = jnp.einsum("ngm,nmd->ngd", comb_m,
                   ye.reshape(n, E * cap, d).astype(jnp.float32))
    return y.astype(x.dtype).reshape(B, S, d), aux
