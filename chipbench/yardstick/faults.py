"""Faults planted in the round cell's timed path, each by name.

``plant(name, setattr)`` breaks the program in place through ``setattr``
(``pytest``'s ``monkeypatch.setattr``, or the builtin where nothing has to
be undone) and clears JAX's caches, so programs traced before are traced
again with the fault in them.  The tests see each one make a run
incorrect; ``calibrate.py --fault`` reads the compared numbers under it
at the cell's own size.
"""
from __future__ import annotations

import dataclasses

ROUND = ("em_state_unchanged", "em_half_batch", "payload_altered",
         "head_unchanged", "head_half_batch", "head_half_steps",
         "broker_half_folded")


def _fit_with(real, rows=None, n_iter=None):
    """The classwise fit with its rows or its iterations changed."""
    def fit(key, feats, labels, n_classes, cfg):
        import jax.numpy as jnp
        if rows == "first_half":
            n = labels.shape[0]
            labels = jnp.where(jnp.arange(n) < n // 2, labels, -1)
        if n_iter is not None:
            cfg = dataclasses.replace(cfg, n_iter=n_iter)
        return real(key, feats, labels, n_classes, cfg)
    return fit


def plant(name: str, set_attr=setattr) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import gmm as G
    from repro.core import head as H
    from repro.fl import api as FA
    from repro.fl import ingest as IG

    if name == "em_state_unchanged":
        # EM hands back its seeding: no iteration runs
        set_attr(G, "fit_classwise_gmms",
                 _fit_with(G.fit_classwise_gmms, n_iter=0))
    elif name == "em_half_batch":
        # each class is fitted to the first half of the client's rows
        set_attr(G, "fit_classwise_gmms",
                 _fit_with(G.fit_classwise_gmms, rows="first_half"))
    elif name == "payload_altered":
        real = FA.encode_message

        def encode(*a, **k):
            msg = real(*a, **k)
            raw = bytearray(msg.payload)
            raw[len(raw) // 2] ^= 0x40       # one bit of a mean, on the wire
            return dataclasses.replace(msg, payload=bytes(raw))
        set_attr(FA, "encode_message", encode)
    elif name == "head_unchanged":
        real = FA.FedSession.aggregate_from_broker

        def aggregate(self, key, broker, *a, **k):
            res = real(self, key, broker, *a, **k)
            d, C = res.model["w"].shape
            return dataclasses.replace(res, model=H.init_head(key, d, C))
        set_attr(FA.FedSession, "aggregate_from_broker", aggregate)
    elif name == "head_half_batch":
        # each Adam step's loss is the mean over the first half of its
        # minibatch; the rest is left out
        real = H._xent

        def xent(params, feats, labels, weights):
            n = weights.shape[0]
            return real(params, feats, labels,
                        jnp.where(jnp.arange(n) < n // 2, weights, 0.0))
        set_attr(H, "_xent", xent)
    elif name == "head_half_steps":
        real = H.fused_gmm_steps

        def steps(key, pi, mu, cov, slot_labels, counts, n_classes, cfg,
                  cov_type):
            cfg = dataclasses.replace(cfg, n_steps=cfg.n_steps // 2)
            return real(key, pi, mu, cov, slot_labels, counts, n_classes,
                        cfg, cov_type)
        set_attr(H, "fused_gmm_steps", steps)
    elif name == "broker_half_folded":
        # the broker folds every other admitted message and drops the rest
        real = IG.fold_messages

        def fold(state, items, samples_per_class=None):
            return real(state, list(items)[::2], samples_per_class)
        set_attr(IG, "fold_messages", fold)
    else:
        raise KeyError(f"no fault {name!r}; known: {ROUND}")
    jax.clear_caches()
