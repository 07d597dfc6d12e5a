"""Model assembly for all assigned architecture families.

One entry point per lifecycle stage:

  * ``init_params(cfg, key)``        — parameter pytree (scanned layer stacks)
  * ``forward(cfg, params, batch)``  — logits (+ MoE aux, + new cache)
  * ``init_cache(cfg, batch, seq)``  — decode-time state (KV / SSM / hybrid)
  * ``loss_fn(cfg, params, batch)``  — training loss + metrics
  * ``features(cfg, params, batch)`` — pooled d_model features (the ``f`` in
    the paper's ``w = h ∘ f``): every architecture doubles as a FedPFT
    foundation-model feature extractor.

Families:
  dense / moe       — pre-norm transformer, GQA attention, MLP or MoE
  vlm               — same decoder + stubbed image-patch prefix
  encoder           — bidirectional transformer, masked-prediction objective
  ssm               — RWKV6 stack (attention-free)
  hybrid            — Mamba2 stack + ONE shared attention block applied every
                      ``attn_every`` layers (zamba2-style weight sharing);
                      or, with ``layer_pattern``, layers of their own kind
                      period after period (granite-4.0-h): each a Mamba-2 or
                      NoPE attention mixer, then an MLP, every branch scaled
                      by ``residual_scale``
"""
from __future__ import annotations

import itertools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import rwkv as rwkv_mod
from repro.models import mamba2 as mamba_mod
from repro.models.config import ModelConfig
from repro.models.layers import (attention, dense_init, init_attention,
                                 init_mlp, init_moe, mlp, moe, rms_norm)

Params = Dict[str, Any]

# activation-sharding hook lives in layers.py (moe needs it too);
# re-exported here for the launch layer.
from repro.models.layers import activation_sharding, constrain as _constrain


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_transformer_stack(key, cfg: ModelConfig, n_layers: int, dtype):
    ka, km, kl = jax.random.split(key, 3)
    w = {
        "ln1": jnp.ones((n_layers, cfg.d_model), dtype),
        "ln2": jnp.ones((n_layers, cfg.d_model), dtype),
        **init_attention(ka, cfg, n_layers, dtype),
    }
    if cfg.n_experts:
        w.update(init_moe(km, cfg, n_layers, dtype))
    else:
        w.update(init_mlp(km, cfg, n_layers, dtype))
    return w


def _init_mixer_stack(key, cfg: ModelConfig, n_layers: int, dtype):
    """granite-4.0-h Mamba-2 layers: norm, mixer, norm, MLP."""
    km, kf = jax.random.split(key)
    return {"ln1": jnp.ones((n_layers, cfg.d_model), dtype),
            "ln2": jnp.ones((n_layers, cfg.d_model), dtype),
            "mixer": mamba_mod.init_mamba_mixer(km, cfg, n_layers, dtype),
            **init_mlp(kf, cfg, n_layers, dtype)}


def _init_shared_attn_block(key, cfg: ModelConfig, dtype):
    """Zamba2 shared block: one full transformer block, reused."""
    stacked = _init_transformer_stack(key, cfg, 1, dtype)
    return jax.tree.map(lambda a: a[0], stacked)


def init_params(cfg: ModelConfig, key) -> Params:
    dtype = _dtype(cfg)
    k_emb, k_blocks, k_head, k_front, k_shared = jax.random.split(key, 5)
    p: Params = {}
    d = cfg.d_model

    if cfg.family == "encoder":
        p["frame_proj"] = dense_init(k_emb, (cfg.frame_embed_dim, d), dtype)
        p["mask_emb"] = dense_init(k_front, (d,), dtype, scale=0.02)
    else:
        p["embed"] = dense_init(k_emb, (cfg.vocab_size, d), dtype, scale=0.02)
    if cfg.family == "vlm":
        p["img_proj"] = dense_init(k_front, (cfg.img_embed_dim, d), dtype)

    if cfg.family == "ssm":
        p["blocks"] = rwkv_mod.init_rwkv_block(k_blocks, cfg, cfg.n_layers,
                                               dtype)
    elif cfg.layer_pattern:
        n_mix, n_attn = hybrid_counts(cfg)
        p["blocks"] = {
            "mamba": _init_mixer_stack(k_blocks, cfg, n_mix, dtype),
            "attn": _init_transformer_stack(k_shared, cfg, n_attn, dtype)}
    elif cfg.family == "hybrid":
        p["blocks"] = mamba_mod.init_mamba_block(k_blocks, cfg, cfg.n_layers,
                                                 dtype)
        p["shared_attn"] = _init_shared_attn_block(k_shared, cfg, dtype)
    else:
        p["blocks"] = _init_transformer_stack(k_blocks, cfg, cfg.n_layers,
                                              dtype)

    p["final_norm"] = jnp.ones((d,), dtype)
    p["lm_head"] = dense_init(k_head, (d, cfg.vocab_size), dtype)
    return p


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------


def _kv_shape(cfg: ModelConfig, n, batch, max_seq, window):
    S = min(max_seq, window) if window else max_seq
    return (n, batch, S, cfg.n_kv_heads, cfg.head_dim)


def hybrid_counts(cfg: ModelConfig):
    """(Mamba-2 layers, attention uses) of a hybrid: zamba2 runs its shared
    block after every ``attn_every`` layers; a ``layer_pattern`` counts
    its kinds over the periods."""
    if cfg.layer_pattern:
        periods = cfg.n_layers // len(cfg.layer_pattern)
        return (periods * cfg.layer_pattern.count("m"),
                periods * cfg.layer_pattern.count("a"))
    return cfg.n_layers, cfg.n_layers // cfg.attn_every


def _hybrid_cache(cfg: ModelConfig, batch: int, max_seq: int, window: int,
                  dtype):
    n_mix, n_attn = hybrid_counts(cfg)
    kv = _kv_shape(cfg, n_attn, batch, max_seq, window)
    return {"mamba": mamba_mod.init_mamba_state(cfg, n_mix, batch),
            "kv": {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}}


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               window: int = 0) -> Any:
    """Decode-time state sized for ``max_seq`` context."""
    dtype = _dtype(cfg)
    if cfg.family == "ssm":
        return rwkv_mod.init_rwkv_state(cfg, batch)
    if cfg.family == "hybrid":
        return _hybrid_cache(cfg, batch, max_seq, window, dtype)
    kv = _kv_shape(cfg, cfg.n_layers, batch, max_seq, window)
    return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}


# ---------------------------------------------------------------------------
# block application (scan over layers)
# ---------------------------------------------------------------------------


def _residual(cfg: ModelConfig, x, y):
    return x + (y if cfg.residual_scale == 1.0 else y * cfg.residual_scale)


def _ffn(cfg: ModelConfig, x, w):
    """A layer's second half: pre-norm MLP (or MoE) and its residual."""
    xn2 = rms_norm(x, w["ln2"], cfg.norm_eps)
    if cfg.n_experts:
        y, aux = moe(xn2, w, cfg)
    else:
        y, aux = mlp(xn2, w, cfg), jnp.zeros((), jnp.float32)
    return _residual(cfg, x, y), aux


def _transformer_block(cfg: ModelConfig, x, w, cache_l, *, positions, window,
                       use_cache, segment_ids=None):
    xn = rms_norm(x, w["ln1"], cfg.norm_eps)
    attn_out, new_cache = attention(xn, w, cache_l, cfg, positions=positions,
                                    window=window, use_cache=use_cache,
                                    segment_ids=segment_ids)
    x, aux = _ffn(cfg, _residual(cfg, x, attn_out), w)
    return x, new_cache, aux


def _mixer_layer(cfg: ModelConfig, x, w, state_l, *, use_cache,
                 segment_ids=None, positions=None):
    """A granite-4.0-h Mamba-2 layer: the attention layer's shape with the
    Mamba-2 mixer in attention's place."""
    y, new_state = mamba_mod.mamba_mixer(
        cfg, rms_norm(x, w["ln1"], cfg.norm_eps), w["mixer"], state_l,
        use_cache=use_cache, segment_ids=segment_ids, positions=positions)
    x, _ = _ffn(cfg, _residual(cfg, x, y), w)
    return x, new_state


def _scan_stack(body, x, stacked, *, unroll: bool = False):
    """scan ``body(x, per_layer) -> (x, ys)`` over the leading layer axis.

    ``unroll=True`` emits a python loop instead of ``lax.scan`` — used by
    the dry-run so HLO cost analysis sees every layer (XLA counts a while
    body once regardless of trip count).
    """
    if not unroll:
        def step(carry, inp):
            return body(carry, inp)
        return jax.lax.scan(step, x, stacked)
    L = jax.tree.leaves(stacked)[0].shape[0]
    ys = []
    for i in range(L):
        x, y = body(x, jax.tree.map(lambda a: a[i], stacked))
        ys.append(y)
    return x, jax.tree.map(lambda *zs: jnp.stack(zs), *ys)


def _run_transformer(cfg: ModelConfig, x, blocks, cache, *, positions,
                     window, use_cache, segment_ids=None):
    def body(carry, inp):
        w_l, cache_l = inp
        y, new_cache, aux = _transformer_block(
            cfg, carry, w_l, cache_l, positions=positions, window=window,
            use_cache=use_cache, segment_ids=segment_ids)
        return _constrain(y), (new_cache, aux)
    if cfg.remat:
        body = jax.checkpoint(body)
    x, (new_cache, aux) = _scan_stack(body, x, (blocks, cache),
                                      unroll=not cfg.scan_layers)
    return x, new_cache, jnp.sum(aux)


def _run_rwkv(cfg: ModelConfig, x, blocks, state, *, use_cache):
    def body(carry, inp):
        w_l, st_l = inp
        y, new_st = rwkv_mod.rwkv_block(cfg, carry, w_l, st_l,
                                        use_cache=use_cache)
        return _constrain(y), new_st
    if cfg.remat:
        body = jax.checkpoint(body)
    x, new_state = _scan_stack(body, x, (blocks, state),
                               unroll=not cfg.scan_layers)
    return x, new_state


def _run_pattern(cfg: ModelConfig, x, blocks, cache, *, positions, window,
                 use_cache, segment_ids=None):
    """granite-4.0-h: ``cfg.layer_pattern`` period after period.  Scans
    over the periods; inside one, over each run of consecutive layers of
    one kind.  Layer stacks and their states keep one flat layer axis per
    kind (``blocks["mamba"]``, ``blocks["attn"]``) in layer order."""
    pat = cfg.layer_pattern
    periods = cfg.n_layers // len(pat)
    runs = [(kind, len(list(g))) for kind, g in itertools.groupby(pat)]

    def by_period(tree, kind):
        per = pat.count(kind)
        return jax.tree.map(
            lambda a: a.reshape((periods, per) + a.shape[1:]), tree)

    def flat(tree):
        return jax.tree.map(
            lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]),
            tree)

    def mixer_body(carry, inp):
        y, new_st = _mixer_layer(cfg, carry, *inp, use_cache=use_cache,
                                 segment_ids=segment_ids, positions=positions)
        return _constrain(y), new_st

    def attn_body(carry, inp):
        y, new_kv, _ = _transformer_block(
            cfg, carry, *inp, positions=positions, window=window,
            use_cache=use_cache, segment_ids=segment_ids)
        return _constrain(y), new_kv
    if cfg.remat:
        mixer_body = jax.checkpoint(mixer_body)
        attn_body = jax.checkpoint(attn_body)
    bodies = {"m": mixer_body, "a": attn_body}

    def period(x, inp):
        stacks = {"m": (inp[0], inp[1]), "a": (inp[2], inp[3])}
        done = {"m": [], "a": []}
        at = {"m": 0, "a": 0}
        for kind, n in runs:
            run = jax.tree.map(lambda a: a[at[kind]:at[kind] + n],
                               stacks[kind])
            x, new = _scan_stack(bodies[kind], x, run,
                                 unroll=not cfg.scan_layers)
            done[kind].append(new)
            at[kind] += n
        cat = {k: jax.tree.map(lambda *a: jnp.concatenate(a), *v)
               for k, v in done.items()}
        return x, (cat["m"], cat["a"])

    x, (new_st, new_kv) = _scan_stack(
        period, x, (by_period(blocks["mamba"], "m"),
                    by_period(cache["mamba"], "m"),
                    by_period(blocks["attn"], "a"),
                    by_period(cache["kv"], "a")),
        unroll=not cfg.scan_layers)
    return x, {"mamba": flat(new_st), "kv": flat(new_kv)}


def _run_hybrid(cfg: ModelConfig, x, params, cache, *, positions, window,
                use_cache, segment_ids=None):
    """Mamba2 stack with the shared attention block every ``attn_every``
    layers. Layer l counts 0-based; the shared block runs after layers
    attn_every-1, 2·attn_every-1, … (n_uses times).  With a
    ``layer_pattern``: :func:`_run_pattern`."""
    if cfg.layer_pattern:
        return _run_pattern(cfg, x, params["blocks"], cache,
                            positions=positions, window=window,
                            use_cache=use_cache, segment_ids=segment_ids)
    A = cfg.attn_every
    n_uses = cfg.n_layers // A
    tail = cfg.n_layers - n_uses * A
    blocks = params["blocks"]
    shared = params["shared_attn"]
    mamba_state = cache["mamba"]
    shared_kv = cache["kv"]

    def mamba_body(carry, inp):
        w_l, st_l = inp
        y, new_st = mamba_mod.mamba_block(cfg, carry, w_l, st_l,
                                          use_cache=use_cache,
                                          segment_ids=segment_ids,
                                          positions=positions)
        return _constrain(y), new_st
    if cfg.remat:
        mamba_body = jax.checkpoint(mamba_body)

    def seg_slice(tree, lo, hi):
        return jax.tree.map(lambda a: a[lo:hi], tree)

    def segment(carry, inp):
        x = carry
        seg_w, seg_st, kv_l = inp
        x, new_st = _scan_stack(mamba_body, x, (seg_w, seg_st),
                                unroll=not cfg.scan_layers)
        # shared attention block (weights shared; per-use KV cache)
        y, new_kv, _ = _transformer_block(
            cfg, x, shared, kv_l, positions=positions, window=window,
            use_cache=use_cache, segment_ids=segment_ids)
        return y, (new_st, new_kv)

    main_w = jax.tree.map(
        lambda a: a[: n_uses * A].reshape((n_uses, A) + a.shape[1:]), blocks)
    main_st = jax.tree.map(
        lambda a: a[: n_uses * A].reshape((n_uses, A) + a.shape[1:]),
        mamba_state)
    x, (new_main_st, new_kv) = _scan_stack(segment, x,
                                           (main_w, main_st, shared_kv),
                                           unroll=not cfg.scan_layers)
    new_main_st = jax.tree.map(
        lambda a: a.reshape((n_uses * A,) + a.shape[2:]), new_main_st)
    if tail:
        tail_w = seg_slice(blocks, n_uses * A, cfg.n_layers)
        tail_st = seg_slice(mamba_state, n_uses * A, cfg.n_layers)
        x, new_tail_st = _scan_stack(mamba_body, x, (tail_w, tail_st),
                                     unroll=not cfg.scan_layers)
        new_state = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=0), new_main_st,
            new_tail_st)
    else:
        new_state = new_main_st
    return x, {"mamba": new_state, "kv": new_kv}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _embed_inputs(cfg: ModelConfig, params, batch):
    """Returns (x, positions). For the VLM, image patches prefix the text."""
    if cfg.family == "encoder":
        x = batch["frames"].astype(_dtype(cfg)) @ params["frame_proj"]
        if "mask" in batch:
            m = batch["mask"][..., None]
            x = jnp.where(m, params["mask_emb"].astype(x.dtype), x)
        return x, jnp.arange(x.shape[1])
    tok = params["embed"][batch["tokens"]]
    if cfg.embed_scale != 1.0:
        tok = tok * cfg.embed_scale
    if cfg.family == "vlm" and "img" in batch:
        img = batch["img"].astype(_dtype(cfg)) @ params["img_proj"]
        x = jnp.concatenate([img, tok], axis=1)
    else:
        x = tok
    return x, jnp.arange(x.shape[1])


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array], *,
            cache: Any = None, positions: Optional[jax.Array] = None,
            window: int = 0, use_cache: bool = False):
    """Returns (logits, aux_loss, new_cache).

    ``positions``: absolute positions of the supplied tokens — required when
    ``use_cache`` (decode/continued-prefill); defaults to ``arange(S)``.
    """
    x, default_pos = _embed_inputs(cfg, params, batch)
    x = _constrain(x)
    positions = default_pos if positions is None else positions
    B, S, d = x.shape
    aux = jnp.zeros((), jnp.float32)

    if cfg.family == "ssm":
        state = cache if cache is not None else rwkv_mod.init_rwkv_state(
            cfg, B)
        x, new_cache = _run_rwkv(cfg, x, params["blocks"], state,
                                 use_cache=use_cache)
    elif cfg.family == "hybrid":
        st = cache if cache is not None else _hybrid_cache(cfg, B, S, window,
                                                           x.dtype)
        x, new_cache = _run_hybrid(cfg, x, params, st, positions=positions,
                                   window=window, use_cache=use_cache)
    else:
        if cache is None:
            z = jnp.zeros((cfg.n_layers, B, 0, cfg.n_kv_heads, cfg.head_dim),
                          x.dtype)
            cache_in, uc = {"k": z, "v": z}, False
        else:
            cache_in, uc = cache, use_cache
        x, new_cache, aux = _run_transformer(
            cfg, x, params["blocks"], cache_in, positions=positions,
            window=window, use_cache=uc)
        if cache is None:
            new_cache = None

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _constrain((x @ params["lm_head"]).astype(jnp.float32),
                        "logits")
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return logits, aux, new_cache


# ---------------------------------------------------------------------------
# losses / features
# ---------------------------------------------------------------------------


def _xent(logits, labels, valid=None):
    lp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
    if valid is None:
        return -jnp.mean(ll)
    valid = valid.astype(jnp.float32)
    return -jnp.sum(ll * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            window: int = 0):
    """Training loss. Batch keys per family:
      LM (dense/moe/ssm/hybrid): tokens (B,S), labels (B,S)
      vlm: tokens, img, labels — labels align with the TEXT tokens only
      encoder: frames (B,S,F), mask (B,S) bool, targets (B,S)
    """
    logits, aux, _ = forward(cfg, params, batch, window=window)
    if cfg.family == "encoder":
        loss = _xent(logits, batch["targets"], batch["mask"])
    elif cfg.family == "vlm":
        text_logits = logits[:, cfg.n_img_tokens:]
        loss = _xent(text_logits, batch["labels"])
    else:
        loss = _xent(logits, batch["labels"])
    total = loss + aux
    return total, {"xent": loss, "aux": aux}


def final_hidden(cfg: ModelConfig, params: Params,
                 batch: Dict[str, jax.Array],
                 segment_ids: Optional[jax.Array] = None,
                 positions: Optional[jax.Array] = None):
    """Post-norm final hidden states ``(B, S, d)`` — the pooling-free body
    of :func:`features`.  The serving layer pools these under a length
    mask (``serve.make_feature_step``) so right-padded batches extract
    exactly the unpadded features: every decode-capable family is causal
    (attention) or left-to-right (the RWKV and Mamba-2 recurrences and the
    Mamba-2 causal conv, in both hybrid layouts: zamba2's shared block and
    granite-4.0-h's layer pattern), so a position's hidden state never
    depends on later pad tokens.

    Packed rows: ``segment_ids`` ``(B, S)`` names each token's sequence
    within its row and ``positions`` ``(B, S)`` its position within that
    sequence.  Attention then stays inside each segment and rope counts
    from each segment's start; in either hybrid the Mamba-2 mixer's conv
    reads no history and its SSD restarts from a zero state at each
    segment start.  So every segment's hidden states are those of its
    sequence alone.  ``ssm`` (RWKV) does not take them: its recurrent
    state would carry one segment into the next.
    Without them the traced program is the unpacked one."""
    x, default_pos = _embed_inputs(cfg, params, batch)
    positions = default_pos if positions is None else positions
    if segment_ids is not None and cfg.family == "ssm":
        raise ValueError(f"final_hidden: {cfg.family} carries recurrent "
                         "state along a row — packed segments would mix")
    if cfg.family == "ssm":
        state = rwkv_mod.init_rwkv_state(cfg, x.shape[0])
        x, _ = _run_rwkv(cfg, x, params["blocks"], state, use_cache=False)
    elif cfg.family == "hybrid":
        st = _hybrid_cache(cfg, x.shape[0], x.shape[1], 0, x.dtype)
        x, _ = _run_hybrid(cfg, x, params, st, positions=positions, window=0,
                           use_cache=False, segment_ids=segment_ids)
    else:
        cache_in = {"k": jnp.zeros((cfg.n_layers, x.shape[0], 0,
                                    cfg.n_kv_heads, cfg.head_dim), x.dtype)}
        cache_in["v"] = cache_in["k"]
        x, _, _ = _run_transformer(cfg, x, params["blocks"], cache_in,
                                   positions=positions, window=0,
                                   use_cache=False, segment_ids=segment_ids)
    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def features(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array]):
    """Mean-pooled final hidden state — the FedPFT foundation feature map."""
    x = final_hidden(cfg, params, batch)
    return jnp.mean(x.astype(jnp.float32), axis=1)
