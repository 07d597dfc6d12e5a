"""The server phase as a pure, AOT-compilable **round program** (DESIGN.md §11).

``FedSession`` trains the global head from a flat mixture-slot stack.  This
module holds that server phase — slot stack → ``head.fused_gmm_steps`` — as
:func:`round_program`, a jitted function of arrays plus ONE static
:class:`CohortSignature`, so ``launch.aot_cache`` can lower+compile it ahead
of time per canonical signature and serve every matching cohort from the
executable cache.

The program has one input format, the decoded slot stack: ``pi (M, K)``,
``mu (M, K, d)``, ``cov (M, K, …)`` unpacked f32, ``slot_labels (M,)`` and
``counts (M,)``.  Every route builds it the same way — the message list
through ``fl.api.fused_slot_stack`` (one row per present (client, class)
slot, ``samples_per_class`` already applied), the streaming reservoir
through ``IngestState.padded_stack()`` at ``M == capacity``.

Zero-count rows anywhere in the stack are exact no-ops under the fused
trainer (f32 cumulative mass adds 0.0 exactly; ``gmm.draw_slots``'
``searchsorted(side="right")`` never selects a zero-mass row), so the
leading :func:`gmm.identity_gmm` pad rows of :func:`pad_slots` train heads
**bit-identical** to the exact-shape stack — the same argument DESIGN.md §9
makes for the reservoir's pad prefix, asserted bitwise in
tests/test_aot_cache.py.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gmm as G
from repro.core import head as H

__all__ = [
    "CohortSignature", "next_pow2", "pad_slots",
    "round_program",
]

def next_pow2(n: int) -> int:
    """Smallest power of two ≥ n (planner's ``_bucket_ceiling`` law, n ≥ 1)."""
    if n < 1:
        raise ValueError(f"next_pow2: n={n} — cohorts have ≥ 1 client")
    return 1 << (int(n) - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class CohortSignature:
    """Everything the round program's compile key depends on.

    ``M`` is the flat slot-row axis; ``C``/``K``/``d``/``cov_type`` are the
    mixture schema (the parameter operands are always f32).  Frozen +
    hashable so it can serve directly as a jit static and a cache key —
    the ``CACHE-KEY`` analyzer rule double-checks hash stability.
    """
    M: int
    C: int
    K: int
    d: int
    cov_type: str

    def __post_init__(self):
        if self.cov_type not in G.COV_TYPES:
            raise ValueError(f"CohortSignature: cov_type={self.cov_type!r} "
                             f"∉ {G.COV_TYPES}")
        if min(self.M, self.C, self.K, self.d) < 1:
            raise ValueError(f"CohortSignature: non-positive axis in "
                             f"(M={self.M}, C={self.C}, K={self.K}, "
                             f"d={self.d})")

    @property
    def cov_shape(self) -> Tuple[int, ...]:
        """Trailing shape of one slot's (unpacked) cov leaf."""
        if self.cov_type == "full":
            return (self.K, self.d, self.d)
        return (self.K, self.d) if self.cov_type == "diag" else (self.K,)

    def canonical(self) -> "CohortSignature":
        """The signature actually compiled for: M rounded up to a power of
        two (planner bucketing idiom).  C/K/d/cov_type stay exact —
        padding K would perturb the in-scan categorical draws and break
        bit-identity; distinct K values are separate grid points instead."""
        return dataclasses.replace(self, M=next_pow2(self.M))


def pad_slots(pi, mu, cov, slot_labels, counts, sig: CohortSignature,
              target: CohortSignature):
    """Pad a slot stack up to the canonical signature.

    Prepends ``target.M − sig.M`` identity-GMM rows (label 0, count 0) —
    pads FIRST, mirroring the reservoir's layout (DESIGN.md §9).  Leading
    zero-count rows are exact no-ops under the fused trainer, so the padded
    stack trains a bit-identical head at the canonical shape.
    """
    if dataclasses.replace(sig, M=target.M) != target:
        raise ValueError(f"pad_slots: {sig} only pads along M, target was "
                         f"{target}")
    if target.M < sig.M:
        raise ValueError(f"pad_slots: target M={target.M} < stack "
                         f"M={sig.M}")
    n_pad = target.M - sig.M
    if n_pad == 0:
        return pi, mu, cov, slot_labels, counts
    ident = G.identity_gmm(sig.K, sig.d, sig.cov_type)
    pad = lambda name, a: np.concatenate([
        np.broadcast_to(np.asarray(ident[name], np.float32),
                        (n_pad,) + np.shape(ident[name])),
        np.asarray(a, np.float32)])
    return (pad("pi", pi), pad("mu", mu), pad("cov", cov),
            np.concatenate([np.zeros((n_pad,), np.int32),
                            np.asarray(slot_labels, np.int32)]),
            np.concatenate([np.zeros((n_pad,), np.int32),
                            np.asarray(counts, np.int32)]))


@partial(jax.jit, static_argnames=("sig", "head_cfg"))
def round_program(key, pi, mu, cov, counts, slot_labels, *,
                  sig: CohortSignature, head_cfg: H.HeadConfig):
    """The whole server phase as one pure function of arrays + statics.

    Inputs are the flat slot stack (module docstring); ``counts`` are the
    per-slot draw counts with ``samples_per_class`` already applied.  Every
    shape this traces is a pure function of ``sig`` — the invariant
    ``launch.aot_cache`` keys on and ``analysis.compile``'s ``CACHE-KEY``
    rule enforces.  Returns ``(head params, per-step loss trace)``.
    """
    return H.fused_gmm_steps(key, pi.astype(jnp.float32),
                             mu.astype(jnp.float32),
                             cov.astype(jnp.float32), slot_labels,
                             counts.astype(jnp.int32), sig.C, head_cfg,
                             sig.cov_type)
