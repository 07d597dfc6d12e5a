"""Seeded decoder weights, made on the device in the type they are served in.

The benchmark, not the program, makes the weights: one jitted call builds
the whole pytree in the layout of ``repro.models.model.init_params`` for the
``dense`` family (without ``lm_head``, which feature extraction never
reads).  Each layer's matrices come from ``layer_key(seed, l)`` alone, so
the plain reference can make any one layer again, in float32, without
touching what the program holds.

Each value is a sum of four random bytes, centred: an integer whose
distribution is close to normal (Irwin–Hall), times one float scale.  A
single rounding, with no transcendental function, makes the draw the same
bits whichever program computes it, so the program's stacked weights and
the reference's per-layer ones agree exactly.  Matrices have standard
deviation 1/√fan_in, the embedding 0.02, and every RMSNorm gain is
≈ 1 + 0.1·z: gains that are not all ones make a reference that skips them
disagree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EMBED_SCALE = 0.02
GAIN_SCALE = 0.1

# (name, shape from dims, fan-in) of one layer, in a fixed order
_LAYER = (
    ("ln1", lambda s: (s["d"],), None),
    ("ln2", lambda s: (s["d"],), None),
    ("wq", lambda s: (s["d"], s["h"] * s["dh"]), "d"),
    ("wk", lambda s: (s["d"], s["hk"] * s["dh"]), "d"),
    ("wv", lambda s: (s["d"], s["hk"] * s["dh"]), "d"),
    ("wo", lambda s: (s["h"] * s["dh"], s["d"]), "hdh"),
    ("w_in", lambda s: (s["d"], s["ff"]), "d"),
    ("w_gate", lambda s: (s["d"], s["ff"]), "d"),
    ("w_out", lambda s: (s["ff"], s["d"]), "ff"),
)


def dims(model: dict) -> dict:
    """The widths a layer needs, from a configuration file's keys."""
    return {"d": model["hidden_size"], "h": model["num_attention_heads"],
            "hk": model["num_key_value_heads"], "dh": model["head_dim"],
            "ff": model["intermediate_size"], "V": model["vocab_size"],
            "L": model["num_hidden_layers"]}


def root_key(seed: int):
    """A PRNG key from any whole seed, also one past 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def layer_key(key, layer):
    return jax.random.fold_in(jax.random.fold_in(key, 1), layer)


BYTES_SD = (4 * (256 ** 2 - 1) / 12) ** 0.5     # sd of a sum of 4 bytes


def _centred(key, shape):
    """Sum of four random bytes minus its mean: int32, sd ``BYTES_SD``."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    total = sum((bits >> sh) & 0xFF for sh in (0, 8, 16, 24))
    return total.astype(jnp.int32) - 510


def draw(key, shape, sd):
    """Near-normal values of standard deviation ``sd``, float32."""
    return _centred(key, shape).astype(jnp.float32) * (sd / BYTES_SD)


def gain(key, shape):
    """≈ 1 + GAIN_SCALE·z: the offset is added as an integer, so one
    multiply is the only rounding."""
    unit = round(BYTES_SD / GAIN_SCALE)
    return (_centred(key, shape) + unit).astype(jnp.float32) * (1.0 / unit)


def layer_f32(key, s: dict) -> dict:
    """One layer's weights in float32, before the cast to the served type."""
    out = {}
    for i, (name, shape, fan) in enumerate(_LAYER):
        k = jax.random.fold_in(key, i)
        if fan is None:
            out[name] = gain(k, shape(s))
        else:
            n = {"d": s["d"], "hdh": s["h"] * s["dh"], "ff": s["ff"]}[fan]
            out[name] = draw(k, shape(s), 1.0 / n ** 0.5)
    return out


def embed_f32(key, s: dict):
    return draw(jax.random.fold_in(key, 2), (s["V"], s["d"]), EMBED_SCALE)


def final_norm_f32(key, s: dict):
    return gain(jax.random.fold_in(key, 3), (s["d"],))


def served(x, dtype):
    """The served value of a float32 draw: cast to ``dtype`` and back."""
    return x.astype(dtype).astype(jnp.float32)


def make_params(seed: int, model: dict, dtype=jnp.bfloat16):
    """All weights in one jitted call on the default device."""
    s = dims(model)

    def build(key):
        def one(layer):
            return {k: v.astype(dtype) for k, v in
                    layer_f32(layer_key(key, layer), s).items()}
        blocks = jax.lax.map(one, jnp.arange(s["L"]))
        return {"embed": embed_f32(key, s).astype(dtype), "blocks": blocks,
                "final_norm": final_norm_f32(key, s).astype(dtype)}

    return jax.jit(build)(root_key(seed))
