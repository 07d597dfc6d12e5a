"""ISSUE 8: multi-tenant round latency, cold vs warm AOT cache.

A server hosting many federations sees a stream of cohorts with mixed
signatures (slot count M, cov_type, …).  Without the cache every *distinct* fused
slot-stack shape pays trace+compile inside the request path; with
``FedSession(program_cache=ProgramCache())`` cohorts pad to the canonical
power-of-two grid and every signature compiles exactly once.

Rows:
    compile_bench/cold_round      mean first-touch latency per canonical
                                  signature (compile in the request path)
    compile_bench/warm_round      mean latency over a ≥20-cohort mixed
                                  stream served entirely from the cache
                                  (acceptance: cold ≥ 5× warm, 0 misses)
    compile_bench/nocache_round   the same stream shape-compacted with no
                                  cache — what each NEW slot-stack shape
                                  costs today (skipped under --quick)
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks import common as C

N_CLASSES = 8
D = 32
K = 2


def _messages(M: int, cov_type: str, seed: int):
    """A synthetic homogeneous cohort — GMM params drawn directly (no EM:
    this bench times the SERVER phase only)."""
    from repro.fl import api as FA
    rng = np.random.default_rng(seed)
    codec = FA.QuantizedCodec("bfloat16")
    out = []
    for m in range(M):
        pi = rng.random((N_CLASSES, K)) + 0.1
        pi /= pi.sum(-1, keepdims=True)
        mu = rng.normal(size=(N_CLASSES, K, D))
        if cov_type == "full":
            a = rng.normal(size=(N_CLASSES, K, D, D)) * 0.1
            cov = a @ a.transpose(0, 1, 3, 2) + np.eye(D)
        elif cov_type == "diag":
            cov = rng.random((N_CLASSES, K, D)) + 0.5
        else:
            cov = rng.random((N_CLASSES, K)) + 0.5
        counts = rng.integers(0, 60, N_CLASSES)
        counts[rng.integers(0, N_CLASSES)] = 0   # absent classes stay exact
        out.append(FA.encode_message(
            {"pi": pi, "mu": mu, "cov": cov}, counts,
            np.zeros(N_CLASSES), kind="gmm", cov_type=cov_type,
            n_classes=N_CLASSES, codec=codec))
    return out


def _round(sess, seed: int, msgs):
    t0 = time.perf_counter()
    r = sess.server_aggregate(jax.random.PRNGKey(seed), msgs)
    jax.block_until_ready(r.model["w"])
    return (time.perf_counter() - t0) * 1e6, r


def main(quick: bool = False):
    from repro.core import head as H
    from repro.fl.api import FedSession
    from repro.launch.aot_cache import ProgramCache

    # the tenant mix: distinct M (→ two pow2 buckets) × cov families
    tenants = [(3, "diag"), (4, "diag"), (6, "diag"), (8, "diag"),
               (3, "spher"), (4, "spher")]
    if quick:
        tenants = [(3, "diag"), (4, "diag")]
    head = H.HeadConfig(n_steps=120, batch_size=128)
    cache = ProgramCache(max_entries=16)
    sess = FedSession(n_classes=N_CLASSES, head=head, program_cache=cache)

    cohorts = [(M, cov, _messages(M, cov, seed=17 * i + M))
               for i, (M, cov) in enumerate(tenants)]

    # cold pass: every cohort once; a round that missed the cache compiled
    # in-path, and the session reports the canonical signature it ran at
    cold, canon = [], set()
    for i, (M, cov, msgs) in enumerate(cohorts):
        us, r = _round(sess, i + 1, msgs)
        canon.add(r.info["compile"]["canonical"])
        if not r.info["compile"]["hit"]:
            cold.append(us)
    cold_mean = float(np.mean(cold))
    C.emit("compile_bench/cold_round", cold_mean,
           f"signatures={len(canon)};compiles={cache.compiles};"
           f"total_compile_us={cache.total_compile_us:.0f}",
           extra={"compiles": cache.compiles,
                  "misses": cache.misses})

    # warm pass: ≥20 mixed-signature cohorts, zero new compiles expected
    n_stream = 8 if quick else 24
    misses0, compiles0 = cache.misses, cache.compiles
    warm = []
    for i in range(n_stream):
        M, cov, msgs = cohorts[i % len(cohorts)]
        us, r = _round(sess, 1000 + i, msgs)
        warm.append(us)
        assert r.info["compile"]["hit"], "warm stream must hit the cache"
    warm_mean = float(np.mean(warm))
    new_misses = cache.misses - misses0
    new_compiles = cache.compiles - compiles0
    C.emit("compile_bench/warm_round", warm_mean,
           f"stream={n_stream};new_misses={new_misses};"
           f"new_compiles={new_compiles};"
           f"cold_over_warm={cold_mean / max(warm_mean, 1e-9):.1f}x",
           extra={"hits": cache.hits, "misses": cache.misses,
                  "evictions": cache.evictions,
                  "cold_over_warm": cold_mean / max(warm_mean, 1e-9)})

    # contrast lane: no cache — the compacted slot stack's shape depends on
    # which classes are absent, so even repeat-M cohorts can retrace
    if not quick:
        nosess = FedSession(n_classes=N_CLASSES, head=head)
        nocache = [_round(nosess, 2000 + i, msgs)[0]
                   for i, (_, _, msgs) in enumerate(cohorts)]
        C.emit("compile_bench/nocache_round", float(np.mean(nocache)),
               f"cohorts={len(cohorts)};"
               f"vs_warm={np.mean(nocache) / max(warm_mean, 1e-9):.1f}x")


if __name__ == "__main__":
    main()
