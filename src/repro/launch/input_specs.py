"""ShapeDtypeStruct stand-ins for every (architecture × input-shape × mode).

No device allocation — these are what ``jit(...).lower()`` consumes in the
multi-pod dry-run. The modality carve-out lives here: audio frame / image
patch embeddings are provided pre-computed at the right shape.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models.config import InputShape, ModelConfig

SDS = jax.ShapeDtypeStruct


def long_window(cfg: ModelConfig) -> int:
    """The sub-quadratic window used for long_500k on attention archs."""
    return cfg.sliding_window if cfg.sliding_window > 0 else 8192


def window_for(cfg: ModelConfig, shape: InputShape) -> int:
    """Full attention ≤32k; sliding window only for the 500k decode."""
    if shape.name == "long_500k" and cfg.family not in ("ssm",):
        return long_window(cfg)
    return 0


def batch_specs_for(cfg: ModelConfig, shape: InputShape,
                    mode: str) -> Dict[str, SDS]:
    """The data batch (mode ∈ train|prefill|decode) as ShapeDtypeStructs."""
    B, S = shape.global_batch, shape.seq_len
    ti = jnp.int32
    if mode == "decode":
        return {"tokens": SDS((B, 1), ti)}
    if cfg.family == "encoder":
        batch = {"frames": SDS((B, S, cfg.frame_embed_dim), jnp.float32)}
        if mode == "train":
            batch["mask"] = SDS((B, S), jnp.bool_)
            batch["targets"] = SDS((B, S), ti)
        return batch
    if cfg.family == "vlm":
        s_text = S - cfg.n_img_tokens
        batch = {"tokens": SDS((B, s_text), ti),
                 "img": SDS((B, cfg.n_img_tokens, cfg.img_embed_dim),
                            jnp.float32)}
        if mode == "train":
            batch["labels"] = SDS((B, s_text), ti)
        return batch
    batch = {"tokens": SDS((B, S), ti)}
    if mode == "train":
        batch["labels"] = SDS((B, S), ti)
    return batch


def round_specs_for(sig, mesh=None) -> Tuple[Any, ...]:
    """The FedPFT round program's traced arguments as ShapeDtypeStructs.

    Mirrors :func:`batch_specs_for` for the federation side: what
    ``fl.round.round_program.lower(...)`` consumes in ``launch.aot_cache``
    — the slot stack's positional ``(key, pi, mu, cov, counts,
    slot_labels)``, no device allocation.  ``mesh`` pins every operand
    to the replicated layout (the fused head runs identically on every
    shard, DESIGN.md §5) so the compiled executable's input shardings
    match what ``FedSession`` device_puts at call time.
    """
    sharding = None
    if mesh is not None:
        sharding = jax.sharding.NamedSharding(mesh,
                                              jax.sharding.PartitionSpec())

    def sds(shape, dtype):
        if sharding is None:
            return SDS(shape, dtype)
        return SDS(shape, dtype, sharding=sharding)

    return (sds((2,), jnp.uint32),
            sds((sig.M, sig.K), jnp.float32),
            sds((sig.M, sig.K, sig.d), jnp.float32),
            sds((sig.M,) + sig.cov_shape, jnp.float32),
            sds((sig.M,), jnp.int32),
            sds((sig.M,), jnp.int32))


def params_shapes(cfg: ModelConfig) -> Any:
    return jax.eval_shape(lambda k: M.init_params(cfg, k),
                          SDS((2,), jnp.uint32))


def cache_shapes(cfg: ModelConfig, shape: InputShape) -> Any:
    w = window_for(cfg, shape)
    return jax.eval_shape(
        lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len, w))


def mode_of(cfg: ModelConfig, shape: InputShape) -> str:
    return shape.kind  # "train" | "prefill" | "decode"


def pair_supported(cfg: ModelConfig, shape: InputShape) -> Tuple[bool, str]:
    """(runs?, reason-if-skipped) for one (arch, shape) pair."""
    if shape.kind == "decode" and not cfg.has_decode:
        return False, f"{cfg.name} is encoder-only: no decode step"
    return True, ""
