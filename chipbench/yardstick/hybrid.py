"""granite-4.0-h, the Mamba-2/attention hybrid: its weights, its plain
float32 reference and the count of its work, for the hybrid extraction
cell.

Weights.  Made by the benchmark from the seed, in the layout of
``repro.models.model.init_params`` for a hybrid with a ``layer_pattern``
(``blocks["mamba"]`` and ``blocks["attn"]``, each stacked in layer order;
no ``lm_head``).  Layer ``l`` is drawn from ``weights.layer_key(seed, l)``
alone with ``weights.draw`` and ``weights.gain``, so the reference makes
any one layer again without touching what the program holds.  An
attention layer is the dense decoder layer of ``weights.layer_f32``; a
Mamba-2 layer draws its norms, its MLP and its mixer from the table
below.  A_log = log(1..H), dt_bias = softplus⁻¹ of a log-uniform Δ in
[1e-3, 1e-1] and D = 1 are Mamba-2's initial values, computed on the host
(dt_bias drawn in float64 from the seed), so both sides read the same
bits.

Reference.  The layer of Hugging Face's ``GraniteMoeHybridDecoderLayer``
with no experts, in straightforward ``jax.numpy`` float32, every product at
HIGHEST, one layer at a time:

    h = x + r · mixer(norm₁(x)),   out = h + r · mlp(norm₂(h)),

the mixer NoPE grouped-query attention (softmax scale from the config) or
Mamba-2 (in_proj → [z, xBC, dt]; causal depthwise conv with bias, SiLU;
Δ = softplus(dt + dt_bias), no clamp; the SSD as its sequential recurrence
S_t = exp(Δ_t A) S_{t−1} + Δ_t B_t x_tᵀ, y_t = C_tᵀ S_t + D x_t;
RMSNorm(y · silu(z)); out_proj).  ``quant="fp8"`` is the control: both
operands of every projection in float8 e4m3 under an absmax scale
(``reference.fp8``).  Departures: no ``lm_head`` or ``logits_scaling``
(features never read the logits); seeded random weights.

Count.  The work the real tokens need: 2 per matrix parameter per token;
per Mamba-2 layer and token 4·H·N·P for the recurrence (state update and
read-out) and 2·K·conv_dim for the conv; per attention layer causal QKᵀ
and PV over the L(L+1)/2 query-key pairs of a row of L tokens.
"""
from __future__ import annotations

from functools import partial
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from yardstick import counts as CT
from yardstick import reference as REF
from yardstick import weights as W

HI = REF.HI
F32_LEAVES = ("A_log", "dt_bias", "D")      # served in float32
CONV_B_SD = 0.1


def period(layer_types) -> str:
    """The shortest repeating period of ``layer_types``, as the program's
    ``layer_pattern``: "m" a Mamba-2 layer, "a" attention."""
    kinds = "".join({"mamba": "m", "attention": "a"}[t] for t in layer_types)
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return kinds[:p]
    raise AssertionError("unreachable")


def dims(model: dict) -> dict:
    """The widths of every layer kind, from a configuration file's keys."""
    d = model["hidden_size"]
    di = int(model["mamba_expand"] * d)
    N = model["mamba_d_state"]
    s = dict(W.dims(model), ff=model["shared_intermediate_size"], di=di,
             H=model["mamba_n_heads"], P=model["mamba_d_head"], N=N,
             K=model["mamba_d_conv"], conv=di + 2 * N,
             kinds=period(model["layer_types"]))
    if (s["H"] * s["P"] != di or model["mamba_n_groups"] != 1
            or model["num_local_experts"] or model["mamba_proj_bias"]
            or not model["mamba_conv_bias"] or model["attention_bias"]):
        raise ValueError(f"{model['name']}: not the layer this reference "
                         "writes (one SSM group, no experts, conv bias, no "
                         "projection bias)")
    return s


def layer_ids(s: dict):
    """Layer indices of the Mamba-2 and of the attention layers."""
    kinds = s["kinds"]
    ids = {"m": [], "a": []}
    for layer in range(s["L"]):
        ids[kinds[layer % len(kinds)]].append(layer)
    return np.array(ids["m"], np.int32), np.array(ids["a"], np.int32)


def program_config(model: dict):
    """The program's ``ModelConfig`` for a configuration file.  Where the
    file names a registered architecture, that config, after checking that
    it runs every number the file states."""
    from repro.configs import get_config
    from repro.models.config import ModelConfig
    s = dims(model)
    fields = dict(family="hybrid", n_layers=s["L"], d_model=s["d"],
                  n_heads=s["h"], n_kv_heads=s["hk"], head_dim=s["dh"],
                  d_ff=s["ff"], vocab_size=s["V"], mlp_variant="swiglu",
                  ssm_state=s["N"], ssm_head_dim=s["P"],
                  ssm_expand=model["mamba_expand"], conv_width=s["K"],
                  chunk_size=model["mamba_chunk_size"],
                  layer_pattern=s["kinds"],
                  use_rope=model["position_embedding_type"] != "nope",
                  attn_scale=model["attention_multiplier"],
                  embed_scale=float(model["embedding_multiplier"]),
                  residual_scale=model["residual_multiplier"],
                  norm_eps=model["rms_norm_eps"],
                  dtype=str(jnp.dtype(model["torch_dtype"])))
    if not model.get("arch"):
        return ModelConfig(name=model["name"], remat=False, **fields)
    cfg = get_config(model["arch"])
    wrong = {k: (getattr(cfg, k), v) for k, v in fields.items()
             if getattr(cfg, k) != v}
    if wrong or cfg.n_experts:
        raise ValueError(f"{model['arch']}: the file and the registered "
                         f"config differ (registered, file): {wrong}")
    return cfg


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

# (path, shape from dims, standard deviation from dims, or None for a gain)
# of one Mamba-2 layer, in a fixed order
_MIXER_LAYER = (
    (("ln1",), lambda s: (s["d"],), None),
    (("ln2",), lambda s: (s["d"],), None),
    (("w_in",), lambda s: (s["d"], s["ff"]), lambda s: s["d"] ** -0.5),
    (("w_gate",), lambda s: (s["d"], s["ff"]), lambda s: s["d"] ** -0.5),
    (("w_out",), lambda s: (s["ff"], s["d"]), lambda s: s["ff"] ** -0.5),
    (("mixer", "w_in"),
     lambda s: (s["d"], 2 * s["di"] + 2 * s["N"] + s["H"]),
     lambda s: s["d"] ** -0.5),
    (("mixer", "conv_w"), lambda s: (s["K"], s["conv"]),
     lambda s: s["K"] ** -0.5),
    (("mixer", "conv_b"), lambda s: (s["conv"],), lambda s: CONV_B_SD),
    (("mixer", "gn"), lambda s: (s["di"],), None),
    (("mixer", "w_out"), lambda s: (s["di"], s["d"]),
     lambda s: s["di"] ** -0.5),
)


def dt_bias(seed: int, s: dict) -> np.ndarray:
    """softplus⁻¹(Δ), Δ log-uniform in [1e-3, 1e-1]: (Mamba-2 layers, H)
    float32, drawn on the host in float64 from the seed."""
    rng = np.random.default_rng([int(seed) % 2 ** 64, 15])
    n_mix = len(layer_ids(s)[0])
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (n_mix, s["H"])))
    return (dt + np.log(-np.expm1(-dt))).astype(np.float32)


def mixer_layer_f32(key, s: dict, dtb) -> dict:
    """One Mamba-2 layer's weights in float32, before the cast."""
    a_log = np.log(np.arange(1, s["H"] + 1)).astype(np.float32)
    out = {"mixer": {"A_log": jnp.asarray(a_log), "dt_bias": dtb,
                     "D": jnp.ones((s["H"],), jnp.float32)}}
    for i, (path, shape, sd) in enumerate(_MIXER_LAYER):
        k = jax.random.fold_in(key, i)
        v = W.gain(k, shape(s)) if sd is None else W.draw(k, shape(s), sd(s))
        (out if len(path) == 1 else out[path[0]])[path[-1]] = v
    return out


def served(tree, dtype):
    """The served values of float32 draws: cast to ``dtype`` and back,
    but for the leaves the program keeps in float32."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, v: v if kp[-1].key in F32_LEAVES else W.served(v, dtype),
        tree)


def make_params(seed: int, model: dict, dtype=jnp.bfloat16):
    """All weights in one jitted call on the default device."""
    s = dims(model)
    mix_ids, attn_ids = layer_ids(s)

    def build(key, dtb):
        def mixer(inp):
            layer, b = inp
            return jax.tree_util.tree_map_with_path(
                lambda kp, v: v if kp[-1].key in F32_LEAVES
                else v.astype(dtype),
                mixer_layer_f32(W.layer_key(key, layer), s, b))

        def attn(layer):
            return {k: v.astype(dtype) for k, v in
                    W.layer_f32(W.layer_key(key, layer), s).items()}
        return {"embed": W.embed_f32(key, s).astype(dtype),
                "blocks": {"mamba": jax.lax.map(mixer, (mix_ids, dtb)),
                           "attn": jax.lax.map(attn, attn_ids)},
                "final_norm": W.final_norm_f32(key, s).astype(dtype)}

    return jax.jit(build)(W.root_key(seed), jnp.asarray(dt_bias(seed, s)))


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------


def _mlp(x, w, quant):
    return REF._mm(jax.nn.silu(REF._mm(x, w["w_gate"], quant))
                   * REF._mm(x, w["w_in"], quant), w["w_out"], quant)


def attention(x, w, s, scale, quant=None):
    """Causal grouped-query attention, no position embedding."""
    B, S, _ = x.shape
    h, hk, dh = s["h"], s["hk"], s["dh"]
    q = REF._mm(x, w["wq"], quant).reshape(B, S, hk, h // hk, dh)
    k = REF._mm(x, w["wk"], quant).reshape(B, S, hk, dh)
    v = REF._mm(x, w["wv"], quant).reshape(B, S, hk, dh)
    sc = jnp.einsum("bqkgd,bskd->bkgqs", q, k, precision=HI) * scale
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=HI)
    return REF._mm(o.reshape(B, S, h * dh), w["wo"], quant)


def ssd_sequential(x, dt, A, B, C, D):
    """x: (Bt, T, H, P); dt: (Bt, T, H); A, D: (H,); B, C: (Bt, T, N).
    The recurrence one step at a time, from a zero state."""
    Bt, T, H, P = x.shape

    def step(S, inp):                                 # S: (Bt, H, N, P)
        x_t, dt_t, B_t, C_t = inp
        S = (jnp.exp(dt_t * A)[..., None, None] * S
             + (dt_t[..., None, None] * B_t[:, None, :, None])
             * x_t[:, :, None, :])
        return S, jnp.einsum("bn,bhnp->bhp", C_t, S, precision=HI)

    S0 = jnp.zeros((Bt, H, B.shape[-1], P), jnp.float32)
    _, ys = jax.lax.scan(step, S0, (x.swapaxes(0, 1), dt.swapaxes(0, 1),
                                    B.swapaxes(0, 1), C.swapaxes(0, 1)))
    return ys.swapaxes(0, 1) + D[:, None] * x


def mixer(x, w, s, eps, quant=None):
    """The Mamba-2 mixer over normed inputs x: (Bt, T, d)."""
    Bt, T, _ = x.shape
    di, N, K = s["di"], s["N"], s["K"]
    z, xbc, dt = jnp.split(REF._mm(x, w["w_in"], quant),
                           [di, 2 * di + 2 * N], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, i:i + T] * w["conv_w"][i]
                          for i in range(K)) + w["conv_b"])
    xs, B, C = jnp.split(xbc, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = ssd_sequential(xs.reshape(Bt, T, s["H"], s["P"]), dt,
                       -jnp.exp(w["A_log"]), B, C, w["D"])
    y = REF.rms_norm(y.reshape(Bt, T, di) * jax.nn.silu(z), w["gn"], eps)
    return REF._mm(y, w["w_out"], quant)


@partial(jax.jit, static_argnames=("s", "run", "quant", "dtype"))
def _mixer_layer(key, layer, dtb, x, *, s, run, quant, dtype):
    sd, (eps, r, _) = dict(s), run
    w = served(mixer_layer_f32(W.layer_key(key, layer), sd, dtb), dtype)
    x = x + r * mixer(REF.rms_norm(x, w["ln1"], eps), w["mixer"], sd, eps,
                      quant)
    return x + r * _mlp(REF.rms_norm(x, w["ln2"], eps), w, quant)


@partial(jax.jit, static_argnames=("s", "run", "quant", "dtype"))
def _attn_layer(key, layer, x, *, s, run, quant, dtype):
    sd, (eps, r, scale) = dict(s), run
    w = served(W.layer_f32(W.layer_key(key, layer), sd), dtype)
    x = x + r * attention(REF.rms_norm(x, w["ln1"], eps), w, sd, scale,
                          quant)
    return x + r * _mlp(REF.rms_norm(x, w["ln2"], eps), w, quant)


@partial(jax.jit, static_argnames=("s", "dtype", "scale"))
def _embed(key, tokens, *, s, dtype, scale):
    return W.served(W.embed_f32(key, dict(s)), dtype)[tokens] * scale


def features(seed: int, model: dict, tokens, lengths, quant=None):
    """(B, S) right-padded tokens with real ``lengths`` → (B, d) float32:
    the mean of the final hidden state over each row's real positions.
    Arrays in and out, so it also runs inside a traced function."""
    s = dims(model)
    frozen = tuple(sorted((k, v) for k, v in s.items()))
    run = (float(model["rms_norm_eps"]), float(model["residual_multiplier"]),
           float(model["attention_multiplier"]))
    dtype = jnp.dtype(model["torch_dtype"])
    key = W.root_key(seed)
    dtb = dt_bias(seed, s)
    kinds = s["kinds"]
    x = _embed(key, jnp.asarray(tokens), s=frozen, dtype=dtype,
               scale=float(model["embedding_multiplier"]))
    i_mix = 0
    for layer in range(s["L"]):
        if kinds[layer % len(kinds)] == "m":
            x = _mixer_layer(key, jnp.int32(layer), jnp.asarray(dtb[i_mix]),
                             x, s=frozen, run=run, quant=quant, dtype=dtype)
            i_mix += 1
        else:
            x = _attn_layer(key, jnp.int32(layer), x, s=frozen, run=run,
                            quant=quant, dtype=dtype)
    return REF._pool(key, x, jnp.asarray(lengths, jnp.float32), s=frozen,
                     eps=run[0], dtype=dtype)


# ---------------------------------------------------------------------------
# count
# ---------------------------------------------------------------------------


def layer_params(s: dict):
    """Matrix parameters of a Mamba-2 layer and of an attention layer,
    each with its MLP."""
    mlp = 3 * s["d"] * s["ff"]
    mix = s["d"] * (2 * s["di"] + 2 * s["N"] + s["H"]) + s["di"] * s["d"]
    return mix + mlp, CT.decoder_layer_params(s)


def row_flops(s: dict, length: int) -> float:
    """Forward FLOPs of one row of ``length`` real tokens."""
    n_mix, n_attn = (len(ids) for ids in layer_ids(s))
    p_mix, p_attn = layer_params(s)
    per_mix = (2.0 * p_mix + 4.0 * s["H"] * s["N"] * s["P"]
               + 2.0 * s["K"] * s["conv"]) * length
    per_attn = (2.0 * p_attn * length
                + 2.0 * 2.0 * s["h"] * s["dh"] * length * (length + 1) / 2)
    return n_mix * per_mix + n_attn * per_attn


def flops(model: dict, lengths: Iterable[int]) -> float:
    s = dims(model)
    return sum(row_flops(s, int(n)) for n in lengths)
