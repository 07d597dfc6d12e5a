"""Mamba2 (SSD) mixer — chunked scalar-decay state-space recurrence.

Per head h with state S ∈ R^{N×P} (N = ssm_state, P = ssm_head_dim):
    S_t = a_t · S_{t-1} + (Δ_t B_t) x_tᵀ            a_t = exp(Δ_t · A_h), A_h < 0
    y_t = C_tᵀ S_t + D_h · x_t

Training/prefill uses the chunked parallel form (intra-chunk pairwise decay
products in log space, inter-chunk state carried with ``lax.scan``) — the
same factorization as the SSD paper, which keeps everything matmul-shaped
for the MXU. Decode is the O(1) single-step recurrence.

``mamba_mixer`` is the mixer alone (in_proj → causal depthwise conv → SSD →
gated RMSNorm → out_proj), as both hybrids use it: zamba2's layer
(``mamba_block``) wraps it in a pre-norm and a residual, a granite-4.0-h
layer (``model._mixer_layer``) in its own norms, multipliers and MLP.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import dense_init, rms_norm

def mamba_dims(cfg: ModelConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    P = cfg.ssm_head_dim
    H = d_inner // P
    N = cfg.ssm_state
    return d_inner, H, P, N


def init_mamba_mixer(key, cfg: ModelConfig, n_layers: int, dtype):
    """Stacked mixer weights.  A and Δ start as Mamba-2's: A_log = log h
    for heads h = 1..H, and dt_bias = softplus⁻¹(Δ) for Δ log-uniform in
    [1e-3, 1e-1]."""
    d = cfg.d_model
    d_inner, H, P, N = mamba_dims(cfg)
    conv_dim = d_inner + 2 * N * 1  # x, B, C streams share the conv (grouped)
    L = (n_layers,)
    ks = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(ks[3], L + (H,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        # in_proj → [z (gate), x, B, C, dt]
        "w_in": dense_init(ks[0], L + (d, 2 * d_inner + 2 * N + H), dtype),
        "conv_w": dense_init(ks[1], L + (cfg.conv_width, conv_dim), dtype,
                             scale=0.5),
        "conv_b": jnp.zeros(L + (conv_dim,), dtype),
        # per-head decay scale / dt bias / skip
        "A_log": jnp.broadcast_to(                       # A = -exp(A_log)
            jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)), L + (H,)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),        # softplus⁻¹(Δ)
        "D": jnp.ones(L + (H,), jnp.float32),
        "gn": jnp.ones(L + (d_inner,), dtype),
        "w_out": dense_init(ks[2], L + (d_inner, d), dtype),
    }


def init_mamba_block(key, cfg: ModelConfig, n_layers: int, dtype):
    """zamba2's layer: the mixer and its pre-norm."""
    return {"ln": jnp.ones((n_layers, cfg.d_model), dtype),
            **init_mamba_mixer(key, cfg, n_layers, dtype)}


def ssd_chunked(x, a_log, B, C, S0, chunk: int = 256, segment_ids=None):
    """Chunked SSD. x: (Bt,H,T,P); a_log: (Bt,H,T) per-step log decay (≤0);
    B, C: (Bt,T,N) shared across heads; S0: (Bt,H,N,P).

    Returns y (Bt,H,T,P) and the final state.  A T that is not a multiple
    of the chunk is padded at the end with steps that neither decay nor
    write the state (x = 0, log a = 0), so y[:T] and the state are exact.

    ``segment_ids`` (Bt,T), contiguous runs along each row: the recurrence
    restarts from a zero state at every segment start (S0 enters the
    first segment), and the final state is the last segment's.  Masks
    keep each term inside its segment — the decay pairs (t, s), the
    entering state and the carried one — so no log-decay sentinel is
    needed.  Without them the traced program is the unsegmented one.
    """
    Bt, H, T, P = x.shape
    N = B.shape[-1]
    Cn = min(chunk, T)
    pad = -T % Cn
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
        a_log = jnp.pad(a_log, ((0, 0), (0, 0), (0, pad)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        if segment_ids is not None:
            segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad)),
                                  mode="edge")
    n = (T + pad) // Cn
    xs = x.reshape(Bt, H, n, Cn, P).transpose(2, 0, 1, 3, 4)
    als = a_log.reshape(Bt, H, n, Cn).transpose(2, 0, 1, 3)
    Bs = B.reshape(Bt, n, Cn, N).transpose(1, 0, 2, 3)
    Cs = C.reshape(Bt, n, Cn, N).transpose(1, 0, 2, 3)
    segmented = segment_ids is not None

    def step(carry, inp):
        # carry: S, and with segments the segment the chunk is entered in
        S, prev = carry if segmented else (carry, None)
        xc, alc, Bc, Cc = inp[:4]       # (Bt,H,Cn,P),(Bt,H,Cn),(Bt,Cn,N)×2
        xc = xc.astype(jnp.float32)
        Bc = Bc.astype(jnp.float32)
        Cc = Cc.astype(jnp.float32)
        cw = jnp.cumsum(alc, axis=-1)                     # Σ_{j≤t} log a
        # intra-chunk: y_t += Σ_{s≤t} e^{cw_t - cw_s} (C_t·B_s) x_s
        expo = cw[..., :, None] - cw[..., None, :]        # (Bt,H,Cn,Cn)
        tri = jnp.arange(Cn)[:, None] >= jnp.arange(Cn)[None, :]
        pairs = tri[None, None]
        if segmented:
            sc = inp[4]                                   # (Bt,Cn)
            pairs = pairs & (sc[:, :, None] == sc[:, None, :])[:, None]
        G = jnp.where(pairs, jnp.exp(expo), 0.0)
        CB = jnp.einsum("btn,bsn->bts", Cc, Bc)           # (Bt,Cn,Cn)
        M = G * CB[:, None]                               # (Bt,H,Cn,Cn)
        y = jnp.einsum("bhts,bhsp->bhtp", M, xc)
        # inter-chunk: y_t += C_t e^{cw_t} S0
        Cdec = Cc[:, None] * jnp.exp(cw)[..., None]       # (Bt,H,Cn,N)
        if segmented:
            # only the entering segment reads S; segments are contiguous,
            # so cw_t then spans that segment's steps alone
            Cdec = Cdec * (sc == prev[:, None])[:, None, :, None]
        y += jnp.einsum("bhtn,bhnp->bhtp", Cdec, S)
        # state: S' = e^{cw_last} S + Σ_s e^{cw_last - cw_s} B_s x_sᵀ
        last = cw[..., -1:]                               # (Bt,H,1)
        Bdec = Bc[:, None] * jnp.exp(last[..., None] - cw[..., None])
        carried = jnp.exp(last)[..., None]                # (Bt,H,1,1)
        if segmented:
            # the last token's segment alone writes S'; S carries on only
            # if that segment entered the chunk
            end = sc[:, -1]
            Bdec = Bdec * (sc == end[:, None])[:, None, :, None]
            carried = carried * (end == prev)[:, None, None, None]
        S_new = carried * S + \
            jnp.einsum("bhsn,bhsp->bhnp", Bdec, xc)
        return ((S_new, end) if segmented else S_new), y

    inputs = (xs, als, Bs, Cs)
    S_init = S0.astype(jnp.float32)
    if segmented:
        inputs += (segment_ids.reshape(Bt, n, Cn).transpose(1, 0, 2),)
        S_init = (S_init, segment_ids[:, 0])
    S_fin, ys = jax.lax.scan(step, S_init, inputs)
    if segmented:
        S_fin = S_fin[0]
    y = ys.transpose(1, 2, 0, 3, 4).reshape(Bt, H, T + pad, P)[:, :, :T]
    return y.astype(x.dtype), S_fin


def ssd_decode(x, a_log, B, C, S0):
    """Single-step SSD. x: (Bt,H,P); a_log: (Bt,H); B,C: (Bt,N)."""
    xf = x.astype(jnp.float32)
    Bf, Cf = B.astype(jnp.float32), C.astype(jnp.float32)
    S = jnp.exp(a_log)[..., None, None] * S0 + \
        Bf[:, None, :, None] * xf[:, :, None, :]
    y = jnp.einsum("bn,bhnp->bhp", Cf, S)
    return y.astype(x.dtype), S


def delta(dt_raw, dt_bias):
    """Δ = softplus(dt + dt_bias) in float32, unclamped: Mamba-2's
    time_step_limit is (0, ∞)."""
    return jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias)


def gated_norm(y, z, gn, eps):
    """Mamba-2's RMSNormGated with norm_before_gate=False: gate by silu(z)
    first, then normalise over d_inner (one group), in float32."""
    gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return rms_norm(gated, gn, eps).astype(y.dtype)


def causal_conv(padded, kern, T: int, positions=None):
    """Depthwise causal conv: out_t = Σ_i kern_i · padded_{t+i}, padded the
    (Kw-1)-step history then the T inputs.  Tap i reaches Kw-1-i steps
    back; with ``positions`` (Bt,T), each token's position within its
    segment, it contributes only where that step is in the same segment."""
    Kw = kern.shape[0]
    if positions is None:
        return sum(padded[:, i:i + T] * kern[i] for i in range(Kw))
    return sum(jnp.where((positions >= Kw - 1 - i)[..., None],
                         padded[:, i:i + T], 0) * kern[i] for i in range(Kw))


def mamba_mixer(cfg: ModelConfig, xn, w, state, *, use_cache: bool,
                segment_ids=None, positions=None):
    """The Mamba-2 mixer over normed inputs xn: (Bt,T,d).  state: dict(conv,
    S) ring-free: conv: (Bt, conv_width-1, conv_dim) trailing inputs;
    S: (Bt,H,N,P).  Returns (y (Bt,T,d), new state).

    Packed rows (no cache): ``segment_ids`` (Bt,T) names each token's
    sequence and ``positions`` (Bt,T) its position within it; the conv
    reads no history across a segment start and the SSD restarts from a
    zero state there, so each segment mixes as if alone."""
    if use_cache and segment_ids is not None:
        raise ValueError("mamba_mixer: packed segments take no cache")
    Bt, T, d = xn.shape
    d_inner, H, P, N = mamba_dims(cfg)
    with jax.named_scope("mixer_in_proj"):
        proj = xn @ w["w_in"]
    z, xi, Bv, Cv, dt_raw = jnp.split(
        proj, [d_inner, 2 * d_inner, 2 * d_inner + N, 2 * d_inner + 2 * N],
        axis=-1)

    with jax.named_scope("mixer_conv"):
        # depthwise causal conv over [x, B, C]
        conv_in = jnp.concatenate([xi, Bv, Cv], axis=-1)  # (Bt,T,conv_dim)
        Kw = cfg.conv_width
        hist = state["conv"]                              # (Bt,Kw-1,conv_dim)
        padded = jnp.concatenate([hist.astype(conv_in.dtype), conv_in],
                                 axis=1)
        kern = w["conv_w"]                                # (Kw, conv_dim)
        conv = causal_conv(padded, kern, T,
                           None if segment_ids is None else positions)
        conv = jax.nn.silu(conv + w["conv_b"])
        new_conv_state = padded[:, -(Kw - 1):] if Kw > 1 else hist
        xi, Bv, Cv = jnp.split(conv, [d_inner, d_inner + N], axis=-1)

    with jax.named_scope("ssd"):
        dt = delta(dt_raw, w["dt_bias"])                  # (Bt,T,H)
        A = -jnp.exp(w["A_log"])                          # (H,)
        a_log = (dt * A).transpose(0, 2, 1)               # (Bt,H,T)
        xh = xi.reshape(Bt, T, H, P).transpose(0, 2, 1, 3)  # (Bt,H,T,P)
        # fold dt into the input (standard SSD parameterization)
        xh_dt = xh * dt.transpose(0, 2, 1)[..., None].astype(xh.dtype)

        if T == 1 and use_cache:
            y, S = ssd_decode(xh_dt[:, :, 0], a_log[:, :, 0], Bv[:, 0],
                              Cv[:, 0], state["S"])
            y = y[:, :, None, :]
        else:
            y, S = ssd_chunked(xh_dt, a_log, Bv, Cv, state["S"],
                               chunk=cfg.chunk_size, segment_ids=segment_ids)
        y = y + w["D"][None, :, None, None].astype(y.dtype) * xh
        y = y.transpose(0, 2, 1, 3).reshape(Bt, T, d_inner)

    with jax.named_scope("gated_norm"):
        y = gated_norm(y, z, w["gn"], cfg.norm_eps)
    with jax.named_scope("mixer_out_proj"):
        out = y @ w["w_out"]
    return out, {"conv": new_conv_state, "S": S}


def mamba_block(cfg: ModelConfig, x, w, state, *, use_cache: bool,
                segment_ids=None, positions=None):
    """zamba2's Mamba-2 layer: pre-norm mixer plus residual."""
    y, new_state = mamba_mixer(cfg, rms_norm(x, w["ln"], cfg.norm_eps), w,
                               state, use_cache=use_cache,
                               segment_ids=segment_ids, positions=positions)
    return x + y, new_state


def init_mamba_state(cfg: ModelConfig, n_layers: int, batch: int):
    d_inner, H, P, N = mamba_dims(cfg)
    conv_dim = d_inner + 2 * N
    return {
        "conv": jnp.zeros((n_layers, batch, cfg.conv_width - 1, conv_dim),
                          jnp.float32),
        "S": jnp.zeros((n_layers, batch, H, N, P), jnp.float32),
    }
