"""jit'd public wrappers around the E-step kernels, dispatched by platform.

The platform picks the path (DESIGN.md §8): compiled Pallas where JAX's
default backend is a TPU, the same kernels in interpret mode where it is
the CPU, and the pure-jnp reference (``ref``) only after a caller asks for
it with ``use_pallas(False)``.  Any other platform is an error rather than
a silent reference run.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.gmm_estep import estep, estep_fused

PALLAS = "pallas"          # compiled Mosaic kernels (TPU)
INTERPRET = "interpret"    # the same kernels, interpreted (CPU)
REFERENCE = "reference"    # the pure-jnp oracles, on request only

_STATE = {"use_pallas": True}


def use_pallas(enable: bool = True):
    """``True`` (the default): the platform's Pallas path — compiled on a
    TPU, interpreted on the CPU.  ``False``: the XLA reference."""
    _STATE["use_pallas"] = bool(enable)


def backend() -> str:
    """The dispatch in force: ``PALLAS``, ``INTERPRET`` or ``REFERENCE``.

    Callers that trace ops.* inside their own ``jit`` must pass this as a
    static argument so their cache keys on the backend — otherwise a
    ``use_pallas`` flip after the first trace is silently ignored
    (core/gmm.fit_gmm_batch does this)."""
    if not _STATE["use_pallas"]:
        return REFERENCE
    platform = jax.default_backend()
    if platform == "tpu":
        return PALLAS
    if platform == "cpu":
        return INTERPRET
    raise RuntimeError(
        f"kernels.ops: no Pallas path for platform {platform!r} — the "
        "kernels are Mosaic (TPU) kernels; call use_pallas(False) to run "
        "the XLA reference explicitly")


def gmm_estep(x, mu, var, pi):
    """(N,d) × (K,d) diag/spher E-step numerators → (N,K).

    ``var`` is diag (K, d) or spher (K,) — both backends expand spher
    internally (the old fallback's ``broadcast_to((K,) → (K, d))`` raised).
    """
    mode = backend()
    if mode == REFERENCE:
        return ref.estep_ref(x, mu, var, pi)
    return estep(x, mu, var, pi, interpret=mode == INTERPRET)


def gmm_estep_fused(x, mu, var, pi):
    """Fused batched E-step → (log-numerators (…,N,K), row logsumexp (…,N)).

    The EM production path (core/gmm.fit_gmm_batch): one call covers a
    whole (B = clients × classes) stack of fits — x may be (Bx, N, d)
    shared by B // Bx consecutive fits — and responsibilities + ``L_EM``
    come out of one tiled pass.
    """
    mode = backend()
    if mode == REFERENCE:
        return ref.estep_fused_ref(x, mu, var, pi)
    return estep_fused(x, mu, var, pi, interpret=mode == INTERPRET)
