"""Micro-benchmark: fused batched E-step vs the pre-kernel reference path
(ISSUE 2).

The E-step dominates every FedPFT round (Algorithm 1, lines 5-10): a cohort
of M clients × C classes is M·C weighted EM fits, each needing the (N, K)
log-responsibility matrix AND its row logsumexp every iteration. The old
hot path dispatched one vmap-over-reference program per client and
re-materialized the (N, K) matrix for the logsumexp; the new path
(``kernels.ops.gmm_estep_fused``) runs the WHOLE (M·C, N, K) stack as one
fused call — one ``pallas_call`` on TPU, one batched XLA program on CPU —
emitting numerators and logsumexp together.

Three rows per (d, cov) point at the paper-scale cohort
(10 clients × 10 classes × K=10):

  per_client    pre-PR cohort structure: one dispatch per client, each a
                vmap over C reference E-steps + a separate logsumexp pass
  vmap_ref      single dispatch, but vmap-over-reference with the
                re-materialized logsumexp (no fusion)
  fused         ops.gmm_estep_fused over the full (M·C, N, K) stack

``derived`` carries the fused row's speedup over per_client (the real
pre-PR baseline). The fused row times the XLA reference
(``use_pallas(False)``): interpret-mode Pallas timings mean nothing, and
no chip number exists for this row yet.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks import common as C
from repro.kernels import ops, ref

M = 10            # clients
CN = 10           # classes
K = 10            # mixture components
N = 200           # samples per client


def _cohort(key, d):
    """One (M·C)-fit stack: per-client features, per-slot GMM params."""
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (M, N, d))
    B = M * CN
    mu = jax.random.normal(ks[1], (B, K, d))
    var = jax.nn.softplus(jax.random.normal(ks[2], (B, K, d))) + 0.1
    pi = jax.nn.softmax(jax.random.normal(ks[3], (B, K)))
    return jax.tree.map(jax.block_until_ready, (x, mu, var, pi))


@jax.jit
def _ref_estep_client(x, mu, var, pi):
    """Pre-PR per-client program: vmap over C class fits, logsumexp as a
    second pass over the materialized (C, N, K) block."""
    lr = jax.vmap(ref.estep_ref, in_axes=(None, 0, 0, 0))(x, mu, var, pi)
    return lr, jax.scipy.special.logsumexp(lr, axis=-1)


@jax.jit
def _vmap_ref_cohort(x, mu, var, pi):
    xb = jnp.repeat(x, CN, axis=0)                        # (M·C, N, d)
    lr = jax.vmap(ref.estep_ref)(xb, mu, var, pi)
    return lr, jax.scipy.special.logsumexp(lr, axis=-1)


def _per_client(x, mu, var, pi):
    outs = []
    for m in range(M):                                     # M dispatches
        outs.append(_ref_estep_client(
            x[m], mu[m * CN:(m + 1) * CN], var[m * CN:(m + 1) * CN],
            pi[m * CN:(m + 1) * CN]))
    return outs


@jax.jit
def _fused(x, mu, var, pi):
    return ops.gmm_estep_fused(x, mu, var, pi)             # one call


def _time(fn, *args, reps: int) -> float:
    out = fn(*args)                                        # warmup/compile
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6


def main(quick: bool = False):
    key = jax.random.PRNGKey(23)
    dims = [256] if quick else [256, 768]
    reps = 2 if quick else 5
    ops.use_pallas(False)          # the fused row times the XLA reference
    try:
        for d in dims:
            x, mu, var, pi = _cohort(jax.random.fold_in(key, d), d)
            us_pc = _time(_per_client, x, mu, var, pi, reps=reps)
            us_vm = _time(_vmap_ref_cohort, x, mu, var, pi, reps=reps)
            us_fu = _time(_fused, x, mu, var, pi, reps=reps)
            tag = f"em_bench/M{M}_C{CN}_K{K}_d{d}"
            C.emit(f"{tag}_per_client", us_pc, f"dispatches={M}")
            C.emit(f"{tag}_vmap_ref", us_vm, "dispatches=1")
            C.emit(f"{tag}_fused", us_fu,
                   f"speedup={us_pc / max(us_fu, 1e-9):.1f}x")
    finally:
        ops.use_pallas(True)       # back to the platform's dispatch


if __name__ == "__main__":
    main()
