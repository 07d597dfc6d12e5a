"""RWKV6 ("Finch") — attention-free, per-channel data-dependent decay.

Recurrence (per head, state S ∈ R^{D×D}):
    out_t = r_tᵀ (S_{t-1} + diag(u) k_t v_tᵀ)
    S_t   = diag(w_t) S_{t-1} + k_t v_tᵀ          w_t = exp(lw_t), lw_t ≤ 0

Training uses a chunked parallel form: within a chunk all pairwise decay
products are computed in log space (exponents ≤ 0, numerically safe), and the
state is carried across chunks with a `lax.scan`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense_init, rms_norm

LW_MIN = -8.0  # clamp per-step log-decay (w >= e^-8): numerics guard


def init_rwkv_block(key, cfg: ModelConfig, n_layers: int, dtype):
    d, H, Dh, ff = cfg.d_model, cfg.n_heads, cfg.ssm_head_dim, cfg.d_ff
    L = (n_layers,)
    ks = jax.random.split(key, 12)
    lora = 64
    return {
        "ln1": jnp.ones(L + (d,), dtype),
        "ln2": jnp.ones(L + (d,), dtype),
        # time-mix
        "mix_r": jnp.full(L + (d,), 0.5, dtype),
        "mix_k": jnp.full(L + (d,), 0.5, dtype),
        "mix_v": jnp.full(L + (d,), 0.5, dtype),
        "mix_w": jnp.full(L + (d,), 0.5, dtype),
        "wr": dense_init(ks[0], L + (d, d), dtype),
        "wk": dense_init(ks[1], L + (d, d), dtype),
        "wv": dense_init(ks[2], L + (d, d), dtype),
        "wg": dense_init(ks[3], L + (d, d), dtype),
        "wo": dense_init(ks[4], L + (d, d), dtype),
        # data-dependent decay (LoRA): lw = -exp(w0 + tanh(x A1) A2)
        "w0": jnp.full(L + (d,), -0.6, jnp.float32),
        "wA1": dense_init(ks[5], L + (d, lora), dtype),
        "wA2": dense_init(ks[6], L + (lora, d), dtype, scale=0.01),
        "u": dense_init(ks[7], L + (H, Dh), jnp.float32, scale=0.5),
        "gn": jnp.ones(L + (d,), dtype),   # per-head group norm gain
        # channel-mix
        "mix_c": jnp.full(L + (d,), 0.5, dtype),
        "wc_in": dense_init(ks[8], L + (d, ff), dtype),
        "wc_out": dense_init(ks[9], L + (ff, d), dtype),
    }


def wkv6_chunked(r, k, v, lw, u, S0, chunk: int = 16):
    """Chunked WKV6. r,k,v,lw: (B,H,T,Dh); u: (H,Dh); S0: (B,H,Dh,Dh).

    Returns out (B,H,T,Dh) and final state.
    """
    B, H, T, Dh = r.shape
    C = min(chunk, T)
    if T % C:
        C = T
    n = T // C
    rs, ks_, vs, lws = (a.reshape(B, H, n, C, Dh).transpose(2, 0, 1, 3, 4)
                        for a in (r, k, v, lw))

    def step(S, xs):
        rc, kc, vc, lwc = (a.astype(jnp.float32) for a in xs)  # (B,H,C,Dh)
        cw = jnp.cumsum(lwc, axis=2)                     # cw[t] = Σ_{j<=t} lw
        cw_prev = cw - lwc                               # cw[t-1]
        # intra-chunk pairwise: P[t,s,d] = r[t,d] k[s,d] e^{cw[t-1,d]-cw[s,d]}
        expo = cw_prev[:, :, :, None, :] - cw[:, :, None, :, :]   # (B,H,C,C,Dh)
        tri = (jnp.arange(C)[:, None] > jnp.arange(C)[None, :])   # s < t
        P = jnp.where(tri[None, None, :, :, None], jnp.exp(expo), 0.0)
        A = jnp.einsum("bhtd,bhsd,bhtsd->bhts", rc, kc, P)
        diag = jnp.einsum("bhtd,bhtd,hd->bht", rc, kc, u.astype(jnp.float32))
        out = jnp.einsum("bhts,bhse->bhte", A, vc)
        out += diag[..., None] * vc
        # inter-chunk: r[t] ⊙ e^{cw[t-1]} against carried state
        rdec = rc * jnp.exp(cw_prev)
        out += jnp.einsum("bhtd,bhde->bhte", rdec, S)
        # state update: S' = diag(e^{cw[-1]}) S + Σ_s diag(e^{cw[-1]-cw[s]}) k_s v_sᵀ
        last = cw[:, :, -1:, :]                          # (B,H,1,Dh)
        kdec = kc * jnp.exp(last - cw)
        S_new = jnp.exp(last[:, :, 0, :])[..., None] * S \
            + jnp.einsum("bhsd,bhse->bhde", kdec, vc)
        return S_new, out

    S_fin, outs = jax.lax.scan(step, S0.astype(jnp.float32), (rs, ks_, vs, lws))
    out = outs.transpose(1, 2, 0, 3, 4).reshape(B, H, T, Dh)
    return out.astype(r.dtype), S_fin


def wkv6_decode(r, k, v, lw, u, S0):
    """Single-token WKV6. r,k,v,lw: (B,H,Dh); S0: (B,H,Dh,Dh)."""
    rc, kc, vc, lwc = (a.astype(jnp.float32) for a in (r, k, v, lw))
    uf = u.astype(jnp.float32)
    out = jnp.einsum("bhd,bhde->bhe", rc, S0) \
        + jnp.einsum("bhd,hd,bhd,bhe->bhe", rc, uf, kc, vc)
    S = jnp.exp(lwc)[..., None] * S0 + kc[..., None] * vc[..., None, :]
    return out.astype(r.dtype), S


def _token_shift(x, last_x):
    """x: (B,T,d); last_x: (B,d) from previous step. Returns x_{t-1} stream."""
    prev = jnp.concatenate([last_x[:, None, :], x[:, :-1, :]], axis=1)
    return prev


def rwkv_block(cfg: ModelConfig, x, w, state, *, use_cache: bool):
    """One RWKV6 layer. state: dict(sx_tm, sx_cm, S) or zeros. x: (B,T,d)."""
    B, T, d = x.shape
    H, Dh = cfg.n_heads, cfg.ssm_head_dim
    # ---- time mix ----
    xn = rms_norm(x, w["ln1"], cfg.norm_eps)
    prev = _token_shift(xn, state["sx_tm"].astype(xn.dtype))
    def lerp(mix):
        return xn + (prev - xn) * mix
    r = (lerp(w["mix_r"]) @ w["wr"]).reshape(B, T, H, Dh).transpose(0, 2, 1, 3)
    k = (lerp(w["mix_k"]) @ w["wk"]).reshape(B, T, H, Dh).transpose(0, 2, 1, 3)
    v = (lerp(w["mix_v"]) @ w["wv"]).reshape(B, T, H, Dh).transpose(0, 2, 1, 3)
    g = jax.nn.silu(lerp(w["mix_r"]) @ w["wg"])
    xw = lerp(w["mix_w"])
    lw = -jnp.exp(w["w0"].astype(jnp.float32)
                  + jnp.tanh(xw @ w["wA1"]).astype(jnp.float32)
                  @ w["wA2"].astype(jnp.float32))
    lw = jnp.clip(lw, LW_MIN, 0.0)
    lw = lw.reshape(B, T, H, Dh).transpose(0, 2, 1, 3)

    if T == 1 and use_cache:
        o, S = wkv6_decode(r[:, :, 0], k[:, :, 0], v[:, :, 0], lw[:, :, 0],
                           w["u"], state["S"])
        o = o[:, :, None, :]
    else:
        o, S = wkv6_chunked(r, k, v, lw, w["u"], state["S"],
                            chunk=cfg.chunk_size)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, d)
    o = rms_norm(o, w["gn"], cfg.norm_eps) * g
    x = x + o @ w["wo"]

    # ---- channel mix ----
    xn2 = rms_norm(x, w["ln2"], cfg.norm_eps)
    prev2 = _token_shift(xn2, state["sx_cm"].astype(xn2.dtype))
    xc = xn2 + (prev2 - xn2) * w["mix_c"]
    h = jnp.square(jax.nn.relu(xc @ w["wc_in"]))
    x = x + h @ w["wc_out"]

    new_state = {"sx_tm": xn[:, -1, :].astype(jnp.float32),
                 "sx_cm": xn2[:, -1, :].astype(jnp.float32), "S": S}
    return x, new_state


def init_rwkv_state(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    d, H, Dh = cfg.d_model, cfg.n_heads, cfg.ssm_head_dim
    L = cfg.n_layers
    return {
        "sx_tm": jnp.zeros((L, batch, d), dtype),
        "sx_cm": jnp.zeros((L, batch, d), dtype),
        "S": jnp.zeros((L, batch, H, Dh, Dh), jnp.float32),
    }
