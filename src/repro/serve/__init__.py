"""Serving steps: prefill (context → cache) and decode (one token against a
``seq_len``-deep cache). These are the functions the decode_32k / long_500k
dry-run shapes lower.

The decode step is O(1) state for SSM/hybrid and O(window) KV for
sliding-window attention — the sub-quadratic paths that make long_500k
feasible (DESIGN.md §4).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.models.config import ModelConfig


def make_prefill_step(cfg: ModelConfig, max_seq: int,
                      window: int = 0) -> Callable:
    """prefill(params, batch) -> (last-token logits, primed cache)."""

    def prefill(params, batch):
        B = jax.tree.leaves(batch)[0].shape[0]
        cache = M.init_cache(cfg, B, max_seq, window)
        S = (batch["tokens"].shape[1] if "tokens" in batch
             else batch["frames"].shape[1])
        n_img = cfg.n_img_tokens if cfg.family == "vlm" else 0
        logits, _, cache = M.forward(
            cfg, params, batch, cache=cache,
            positions=jnp.arange(S + n_img), window=window, use_cache=True)
        return logits[:, -1], cache

    return prefill


def make_decode_step(cfg: ModelConfig, window: int = 0) -> Callable:
    """decode(params, cache, tokens (B,1), pos scalar) -> (logits, cache).

    ``pos`` is the absolute position of the new token (dynamic scalar).
    """
    assert cfg.has_decode, f"{cfg.name} is encoder-only: no decode step"

    def decode(params, cache, tokens, pos):
        logits, _, cache = M.forward(
            cfg, params, {"tokens": tokens}, cache=cache,
            positions=pos[None], window=window, use_cache=True)
        return logits[:, -1], cache

    return decode


def pow2_bucket(n: int, min_bucket: int = 8, max_bucket: int = 256) -> int:
    """Smallest power-of-two ≥ ``n`` clamped to [min_bucket, max_bucket].

    The serving layer pads prompts to these lengths so a stream of
    varied-length prompts triggers at most ``log2(max/min)+1`` prefill
    compiles instead of one per distinct length (the planner's bucketing
    idiom applied to the compile-key axis)."""
    if n < 1:
        raise ValueError(f"pow2_bucket: n={n} — prompts have ≥ 1 token")
    if n > max_bucket:
        raise ValueError(f"pow2_bucket: n={n} exceeds max_bucket="
                         f"{max_bucket} (the cache depth)")
    b = 1 << (int(n) - 1).bit_length()
    return min(max(b, min_bucket), max_bucket)


def pad_to_bucket(tokens, bucket: int):
    """Right-pad a ``(B, L)`` token batch with zeros to ``(B, bucket)``.

    Pad token ids never reach the output: causal attention (and the
    ``kv_valid`` decode mask) hides positions ≥ the real length, and the
    masked steps below index / pool by the real length only.
    """
    L = tokens.shape[-1]
    if L > bucket:
        raise ValueError(f"pad_to_bucket: length {L} > bucket {bucket}")
    if L == bucket:
        return tokens
    return jnp.pad(tokens, [(0, 0)] * (tokens.ndim - 1) + [(0, bucket - L)])


def make_bucketed_prefill_step(cfg: ModelConfig, max_seq: int,
                               window: int = 0) -> Callable:
    """Masked prefill over right-padded prompts (one compile per bucket).

    ``prefill(params, batch, length)`` — ``batch["tokens"]`` is ``(B, S_b)``
    right-padded to a bucket length, ``length`` the real prompt length
    (traced scalar, so it is NOT part of the compile key).  Returns the
    logits at the last *real* token and the primed cache.

    Only valid for attention-cache families with a dense (non-ring) cache:
    the pad positions' K/V land at cache indices ≥ ``length``, which
    causal masking hides during prefill and the decode-time ``kv_valid``
    mask (``kv_pos <= position``) hides afterwards — each decode step
    overwrites index ``pos`` before attending it.  Recurrent caches
    (ssm/hybrid) fold every processed token into O(1) state, so pads
    would corrupt it — callers gate on ``cfg.family`` (see
    ``BatchedServer``).
    """

    def prefill(params, batch, length):
        B = jax.tree.leaves(batch)[0].shape[0]
        cache = M.init_cache(cfg, B, max_seq, window)
        S = batch["tokens"].shape[1]
        n_img = cfg.n_img_tokens if cfg.family == "vlm" else 0
        logits, _, cache = M.forward(
            cfg, params, batch, cache=cache,
            positions=jnp.arange(S + n_img), window=window, use_cache=True)
        last = jax.lax.dynamic_index_in_dim(
            logits, n_img + length - 1, axis=1, keepdims=False)
        return last, cache

    return prefill


def pad_batch(prompts, width: int, n_slots: int):
    """One request per row: ``(n_slots, width)`` tokens, right-padded, and
    ``(n_slots,)`` lengths; rows past the prompts carry length 0."""
    tokens = np.zeros((n_slots, width), np.int32)
    length = np.zeros((n_slots,), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
        length[i] = len(p)
    return tokens, length


def pack_rows(lengths, width: int, n_slots: int):
    """First-fit-decreasing placement of sequences into rows of ``width``.

    Returns ``(row, R)``: ``row[i]`` is the row of sequence ``i`` and ``R``
    the row count, the power of two at or above the rows used, at most
    ``n_slots``.  Ties keep the input order, so a layout is a function of
    the lengths alone."""
    if len(lengths) > n_slots:
        raise ValueError(f"pack_rows: {len(lengths)} sequences > n_slots="
                         f"{n_slots}")
    free: list = []
    row = [0] * len(lengths)
    for i in sorted(range(len(lengths)), key=lambda i: -lengths[i]):
        if lengths[i] > width:
            raise ValueError(f"pack_rows: length {lengths[i]} > width "
                             f"{width}")
        k = next((k for k, f in enumerate(free) if f >= lengths[i]),
                 len(free))
        if k == len(free):
            free.append(width)
        free[k] -= lengths[i]
        row[i] = k
    return row, min(1 << max(len(free) - 1, 0).bit_length(), n_slots)


def pack_batch(prompts, width: int, n_slots: int):
    """Several requests per row: ``(R, width)`` tokens and ``(R, n_slots)``
    segment lengths, the layout a packing feature step takes.

    Request ``i`` is column ``i``: its length sits in the one row that
    holds it (:func:`pack_rows`) and is 0 in every other.  A row holds its
    segments left to right in column order, then padding; rows past the
    packed ones are all padding."""
    row, R = pack_rows([len(p) for p in prompts], width, n_slots)
    tokens = np.zeros((R, width), np.int32)
    length = np.zeros((R, n_slots), np.int32)
    fill = [0] * R
    for i, (p, k) in enumerate(zip(prompts, row)):
        tokens[k, fill[k]:fill[k] + len(p)] = p
        length[k, i] = len(p)
        fill[k] += len(p)
    return tokens, length


def segment_layout(length, width: int):
    """Per-token segment id and position within the segment, ``(R, width)``
    each, of a packed batch's ``(R, G)`` segment lengths.  Tokens past a
    row's segments are padding: segment ``G``, which no column pools."""
    ends = jnp.cumsum(length, axis=1)                         # (R, G)
    p = jnp.arange(width)
    seg = jnp.sum(p[None, :, None] >= ends[:, None, :], axis=-1)
    starts = jnp.concatenate([ends - length, ends[:, -1:]], axis=1)
    return seg, p[None, :] - jnp.take_along_axis(starts, seg, axis=1)


def make_feature_step(cfg: ModelConfig) -> Callable:
    """Masked FedPFT feature extraction over right-padded token batches.

    ``feats(params, tokens, length)`` returns mean-pooled final hidden
    states over the real positions only: exactly ``model.features`` of
    each unpadded sequence.  ``length`` takes one of two layouts:

    * ``(B,)``, one request per row of ``tokens`` ``(B, S_b)``; returns
      ``(B, d_model)``.  Every decode-capable family is causal/left-to-right,
      so pad positions never influence real ones.  Rows with
      ``length == 0`` return zeros.
    * ``(R, G)``, packed (:func:`pack_batch`): row ``r`` of ``tokens``
      ``(R, S_b)`` holds, left to right, the segments of lengths
      ``length[r]``, then padding; column ``g`` is one request, nonzero
      in at most one row.  Returns ``(G, d_model)``, zeros for an empty
      column.  The step derives each token's segment and its position
      within it on the device (:func:`segment_layout`): rope counts from
      the segment's start, attention stays inside the segment, the Mamba-2
      conv and SSD restart at it, and pooling
      is a masked sum per segment in float32 (a matmul at HIGHEST
      precision; the default would round the states to bfloat16 on TPU).

    Packing is exact where every sequence mixer keeps to its segment, and
    ``feats.packs`` says which families pack: ``dense``, and ``hybrid``
    (zamba2's Mamba-2 stack with a shared block, and granite-4.0-h's
    Mamba-2 and NoPE attention layers alike), whose Mamba-2 mixer reads no
    conv history and restarts its SSD state at each segment start.
    ``ssm`` (RWKV) carries its recurrent state along the row, which would
    cross segments, and ``moe``'s capacity routing already makes features
    depend on batch-mates, which sharing rows would make worse.
    """
    assert cfg.has_decode, (
        f"{cfg.name} is encoder-only: bidirectional attention mixes pad "
        "positions into real ones — serve unpadded batches instead")
    packs = cfg.family in ("dense", "hybrid")

    def feats(params, tokens, length):
        if length.ndim == 1:
            h = M.final_hidden(cfg, params, {"tokens": tokens})
            mask = jnp.arange(h.shape[1])[None, :] < length[:, None]
            w = mask.astype(jnp.float32)[..., None]
            return jnp.sum(h.astype(jnp.float32) * w, axis=1) / jnp.maximum(
                length[:, None].astype(jnp.float32), 1.0)
        if not packs:
            raise ValueError(f"make_feature_step: {cfg.family} does not "
                             "pack — pass (B,) lengths")
        seg, pos = segment_layout(length, tokens.shape[1])
        h = M.final_hidden(cfg, params, {"tokens": tokens},
                           segment_ids=seg, positions=pos)
        onehot = (seg[..., None] == jnp.arange(length.shape[1])).astype(
            jnp.float32)                                      # (R, S_b, G)
        sums = jnp.einsum("rsg,rsd->gd", onehot, h.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
        return sums / jnp.maximum(
            jnp.sum(length, axis=0)[:, None].astype(jnp.float32), 1.0)

    feats.packs = packs
    return feats


def make_encode_step(cfg: ModelConfig) -> Callable:
    """Encoder-only 'serving': one full bidirectional encode."""

    def encode(params, batch):
        logits, _, _ = M.forward(cfg, params, batch)
        return logits

    return encode


def greedy_generate(cfg: ModelConfig, params, prompt: jax.Array,
                    n_new: int, max_seq: int, window: int = 0):
    """Host-side autoregressive loop (prefill + n_new decode steps)."""
    prefill = jax.jit(make_prefill_step(cfg, max_seq, window))
    decode = jax.jit(make_decode_step(cfg, window))
    logits, cache = prefill(params, {"tokens": prompt})
    S = prompt.shape[1] + (cfg.n_img_tokens if cfg.family == "vlm" else 0)
    toks = []
    tok = jnp.argmax(logits, -1)[:, None]
    for i in range(n_new):
        toks.append(tok)
        logits, cache = decode(params, cache, tok,
                               jnp.asarray(S + i, jnp.int32))
        tok = jnp.argmax(logits, -1)[:, None]
    return jnp.concatenate(toks, axis=1)
