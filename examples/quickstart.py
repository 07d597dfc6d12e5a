"""Quickstart: one-shot FedPFT through the unified `FedSession` API.

    PYTHONPATH=src python examples/quickstart.py

Ten clients with non-iid (Dirichlet β=0.1) data each fit per-class GMMs
over foundation-model features. The session encodes each summary with a
REAL 16-bit wire codec (the server decodes and computes on the quantized
parameters — `comm_bytes` is the actual payload length), then trains the
global classifier head straight from the decoded mixtures: every Adam
step draws its minibatch from the (client, class) slot stack inside one
jitted scan, so the synthetic feature pool is never materialized. One
round, a fraction of the bytes, near-centralized accuracy.

Serving many federations? Pass `FedSession(program_cache=
launch.aot_cache.ProgramCache())` to AOT-compile each canonical cohort
shape once and serve every later round from the executable cache —
slot stacks pad to power-of-two sizes bit-identically, and warm rounds skip
trace+compile entirely (DESIGN.md §11, benchmarks/compile_bench.py).

Serving the whole loop? `examples/fedpft_service.py` runs FedPFT as a
service: backbone feature extraction and head classification share one
continuous-batching slot pool, GMM messages stream through the ingest
broker, and `close_round` trains the served head through the warm AOT
cache (DESIGN.md §12).

Before sending a change, run the repo's own linter (DESIGN.md §10) —
key discipline, compile churn, kernel + wire contracts:

    PYTHONPATH=src python -m repro.analysis src/repro benchmarks examples
"""
import jax

from repro import data as D
from repro.core import fedpft as FP
from repro.core import gmm as G
from repro.core import head as H
from repro.fl import api as FA
from repro.launch import compile_cache


def main():
    key = jax.random.PRNGKey(0)
    # synthetic stand-in for "CIFAR features from a frozen backbone"
    dcfg = D.DatasetConfig(n_classes=10, n_per_class=200, input_dim=32,
                           class_sep=1.5)
    feats, labels = D.make_dataset(dcfg)
    feats_test, labels_test = D.make_dataset(dcfg, split=1)

    # ---- partition across 10 clients, highly non-iid ----
    parts = D.dirichlet_partition(labels, n_clients=10, beta=0.1)
    clients = [(feats[p], labels[p]) for p in parts if len(p) > 5]

    # ---- one-shot FedPFT: summarizer × codec × topology ----
    sess = FA.FedSession(
        n_classes=dcfg.n_classes,
        summarizer=FA.GMMSummarizer(
            G.GMMConfig(n_components=5, cov_type="diag", n_iter=20)),
        codec=FA.QuantizedCodec("bfloat16"),
        topology=FA.Star(),
        head=H.HeadConfig(n_steps=400, lr=3e-3))
    k_fed, k_cent = jax.random.split(key)
    res = sess.run(k_fed, clients)
    acc = float(H.accuracy(res.model, feats_test, labels_test))
    assert res.info["comm_bytes"] == sum(len(m.payload)
                                         for m in res.messages)

    # ---- centralized oracle (ships raw features) ----
    cfg_v1 = FP.FedPFTConfig(gmm=sess.summarizer.gmm, head=sess.head)
    head_c, info_c = FP.centralized_baseline(k_cent, clients, dcfg.n_classes,
                                             cfg_v1)
    acc_c = float(H.accuracy(head_c, feats_test, labels_test))

    comm = res.info["comm_bytes"]
    plan = res.info["synthesis_plans"][0]
    print(f"FedPFT       acc={acc:.4f}  comm={comm/1e3:8.1f} KB "
          f"({len(res.messages)} encoded messages)")
    print(f"server       head trained from {len(plan.slot_table)} mixture "
          f"slots; the {plan.requested} synthetic features they stand for "
          "were never materialized")
    print(f"Centralized  acc={acc_c:.4f}  comm={info_c['comm_bytes']/1e3:8.1f} KB")
    print(f"→ {info_c['comm_bytes']/comm:.1f}× less "
          f"communication, {abs(acc_c-acc)*100:.2f} pts from the oracle, "
          f"one round.")


if __name__ == "__main__":
    compile_cache.enable()
    main()
