"""The program's view of a configuration file."""
from __future__ import annotations

import dataclasses


def program_config(model: dict):
    """The program's ``ModelConfig`` for a configuration file: its widths
    and depth, and the rotary base it runs (``as_run``, where the program
    departs from the published value).  Where the file names a registered
    architecture, all but the depth must equal the registered config."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.config import ModelConfig
    fields = dict(d_model=model["hidden_size"],
                  n_heads=model["num_attention_heads"],
                  n_kv_heads=model["num_key_value_heads"],
                  head_dim=model["head_dim"],
                  d_ff=model["intermediate_size"],
                  vocab_size=model["vocab_size"],
                  rope_theta=float(model["as_run"]["rope_theta"]),
                  dtype=str(jnp.dtype(model["torch_dtype"])))
    if model.get("arch"):
        cfg = get_config(model["arch"])
        wrong = {k: (getattr(cfg, k), v) for k, v in fields.items()
                 if getattr(cfg, k) != v}
        if wrong or cfg.family != "dense" or cfg.mlp_variant != "swiglu":
            raise ValueError(f"{model['arch']}: the file and the registered "
                             f"config differ (registered, file): {wrong}")
        return dataclasses.replace(cfg, n_layers=model["num_hidden_layers"])
    return ModelConfig(name=model["name"], family="dense",
                       n_layers=model["num_hidden_layers"],
                       mlp_variant="swiglu", remat=False, **fields)
