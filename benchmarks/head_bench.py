"""Micro-benchmark: the server phase end-to-end — fused sampler-in-the-loop
head training vs the materializing path.

The server never needs the synthetic pool, it needs minibatches drawn from
the clients' mixtures.  Two ways to get them, A/B'd on the skewed 10×10
cohort (counts log-spaced 1 → 4096):

* ``pooled``    planner-bucketed synthesis, concatenate every chunk into the
                (Σcounts, d) pool, one ``train_head`` scan over it — peak
                memory carries the whole pool (the heterogeneous-cohort
                fallback);
* ``fused``     ``train_head_from_gmms``: no synthesis at all — every Adam
                step draws its minibatch from the (G, K, …) slot stack
                inside ONE jitted scan.  Zero materialization, one dispatch.

Rows: ``head_bench/skew_M{M}_C{C}_{impl}`` with wall-clock us_per_call and
``dispatches=`` / peak-memory proxies (bytes of the largest resident
synthetic tensor) in the derived column.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from repro.core import head as H
from repro.fl import api as FA
from repro.fl import planner as P

K = 5
D = 64
M, CN = 10, 10


def _make_batch(key):
    ks = jax.random.split(key, 3)
    batch = {
        "pi": jax.nn.softmax(jax.random.normal(ks[0], (M, CN, K))),
        "mu": jax.random.normal(ks[1], (M, CN, K, D)),
        "cov": 0.1 + jax.random.uniform(ks[2], (M, CN, K, D)),
    }
    return jax.tree.map(jax.block_until_ready, batch)


def _skewed_counts(lo=1, hi=4096, seed=3):
    counts = np.geomspace(lo, hi, M * CN).astype(np.int64)
    np.random.RandomState(seed).shuffle(counts)
    return counts.reshape(M, CN)


def _time(fn, reps: int) -> float:
    jax.block_until_ready(jax.tree.leaves(fn())[0])   # warmup / compile
    t0 = time.time()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(jax.tree.leaves(out)[0])
    return (time.time() - t0) / reps * 1e6


def main(quick: bool = False):
    key = jax.random.PRNGKey(23)
    batch = _make_batch(key)
    counts = _skewed_counts()
    cfg = H.HeadConfig(n_steps=150 if quick else 500, lr=3e-3)
    reps = 2 if quick else 3
    # slot stack for the fused path — the same construction FedSession uses
    stack, labels, slot_counts, plan = FA.fused_slot_stack(batch, counts)
    stack = {k: jax.block_until_ready(v) for k, v in stack.items()}

    def run_pooled():
        feats, ys = FA.synthesize_batched(key, batch, counts, "diag")
        head, _ = H.train_head(key, feats, ys, CN, cfg)
        return head

    def run_fused():
        head, _ = H.train_head_from_gmms(key, stack["pi"], stack["mu"],
                                         stack["cov"], labels, slot_counts,
                                         CN, cfg, "diag")
        return head

    us_pool = _time(run_pooled, reps)
    us_fused = _time(run_fused, reps)

    # dispatch counts: synthesis dispatches + head-training dispatches
    disp_pool = plan.n_dispatches + 1          # bucket samples + one scan
    disp_fused = 1                              # one fused device program
    # peak-memory proxy: largest resident synthetic tensor (f32 bytes)
    pool_bytes = plan.requested * D * 4
    stack_bytes = sum(int(np.prod(np.shape(v))) * 4 for v in stack.values())
    # slot stack + one (window, batch, d) noise block + the hoisted
    # (n_steps, batch) int32 slot/component draws
    fused_bytes = (stack_bytes
                   + cfg.noise_window * cfg.batch_size * D * 4
                   + cfg.n_steps * cfg.batch_size * 2 * 4)

    C.emit(f"head_bench/skew_M{M}_C{CN}_pooled", us_pool,
           f"dispatches={disp_pool}:pool_bytes={pool_bytes}")
    C.emit(f"head_bench/skew_M{M}_C{CN}_fused", us_fused,
           f"dispatches={disp_fused}:peak_bytes={fused_bytes}:"
           f"speedup_vs_pooled={us_pool / max(us_fused, 1e-9):.1f}x")


if __name__ == "__main__":
    main()
