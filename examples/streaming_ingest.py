"""Streaming cohort ingestion: M as a streaming axis (DESIGN.md §9).

    PYTHONPATH=src python examples/streaming_ingest.py

Three views of the fl.ingest broker:

1. `FedSession(ingest=IngestConfig(...))` — the streaming Star round.
   Each client's message is produced, folded into a fixed-capacity
   reservoir chunk-at-a-time, and DISCARDED; under capacity the trained
   head is bit-identical to the non-streaming fused session's.
2. The broker driven directly with a deadline: stragglers arriving after
   it are byte-accounted but never folded — the round still closes with a
   valid head over whatever arrived.
3. The memory law: peak resident server bytes at M vs 4M clients with the
   same (capacity, chunk_size) — identical, while the stacked cohort
   would have grown 4×.
"""
import jax
import numpy as np

from repro import data as D
from repro.core import gmm as G
from repro.core import head as H
from repro.fl import api as FA
from repro.fl import ingest as IG
from repro.launch import compile_cache


def make_clients(n_clients, C, d, seed=0):
    dcfg = D.DatasetConfig(n_classes=C, n_per_class=40 * n_clients // C,
                           input_dim=d, class_sep=2.0, seed=seed)
    x, y = D.make_dataset(dcfg)
    parts = D.dirichlet_partition(np.asarray(y), n_clients, beta=0.5)
    return [(x[p], y[p]) for p in parts if len(p) > 5]


def main():
    C, d = 6, 16
    key = jax.random.PRNGKey(0)
    clients = make_clients(12, C, d)

    def session(**kw):
        return FA.FedSession(
            n_classes=C,
            summarizer=FA.GMMSummarizer(
                G.GMMConfig(n_components=2, cov_type="diag", n_iter=12)),
            head=H.HeadConfig(n_steps=250, lr=3e-3), **kw)

    # -- 1. streaming session ≡ non-streaming fused session ---------------
    k_run, k_deadline, k_mem = jax.random.split(key, 3)
    base = session().run(k_run, clients)
    # deliberate same-stream replay: bit-identity below requires both runs
    # to draw from one key
    stream = session(ingest=IG.IngestConfig(  # lint: disable=KEY-REUSE
        chunk_size=4, capacity=256)).run(k_run, clients)
    same = all(np.array_equal(np.asarray(base.model[k]),
                              np.asarray(stream.model[k]))
               for k in ("w", "b"))
    acct = stream.info["ingest"]
    print(f"M={len(clients)} clients, chunk_size=4, capacity=256")
    print(f"  head bit-identical to non-streaming fused run: {same}")
    print(f"  admitted={acct['admitted']}  chunks={acct['chunks_folded']}  "
          f"bytes={acct['admitted_bytes']}  "
          f"peak_resident={acct['peak_resident_bytes']}")

    # -- 2. deadline round with stragglers ---------------------------------
    clock = iter(np.arange(0.0, 100.0, 0.5))   # fake monotonic clock
    broker = IG.IngestBroker(IG.IngestConfig(chunk_size=4, capacity=256,
                                             deadline_s=3.0),
                             C, clock=lambda: next(clock))
    keys = jax.random.split(k_deadline, len(clients) + 1)
    sess = session()
    for i, (k, (f, y)) in enumerate(zip(keys[1:], clients)):
        broker.submit(i, sess.client_update(k, f, y, i))
    state = broker.close()
    acct = broker.accounting()
    pi, mu, cov, labels, counts = state.padded_stack()
    head, _ = H.train_head_from_gmms(jax.random.split(keys[0])[1], pi, mu,
                                     cov, labels, counts, C, sess.head,
                                     state.cov_type)
    print(f"deadline_s=3.0: admitted={acct['admitted']}  "
          f"late={acct['late']}  late_bytes={acct['late_bytes']}  "
          f"head finite={bool(np.isfinite(np.asarray(head['w'])).all())}")

    # -- 3. the memory law: peak bytes independent of M --------------------
    peaks = {}
    for mult, seed in ((1, 1), (4, 2)):
        cohort = make_clients(12 * mult, C, d, seed=seed)
        r = session(ingest=IG.IngestConfig(chunk_size=4, capacity=256)
                    ).run(jax.random.fold_in(k_mem, mult), cohort)
        peaks[len(cohort)] = r.info["ingest"]["peak_resident_bytes"]
    print("peak resident bytes by cohort size:", peaks)


if __name__ == "__main__":
    compile_cache.enable()
    main()
