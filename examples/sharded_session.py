"""Mesh-native FedSession: the one-shot round as real collectives.

    PYTHONPATH=src python examples/sharded_session.py

Simulates an 8-device host (the XLA flag below must be set before jax
initializes — same trick as the multidevice test lane, tests/_spawn.py),
shards an 8-client cohort over the mesh's "data" axis, and runs the whole
round with `FedSession(shards=…)`: each shard fits its clients' classwise
GMMs as one batched EM, the bf16 wire pytree crosses the mesh in a single
all_gather (THE communication round), and the server phase — the head
trained straight from the decoded mixture-slot stack in one fused scan —
runs replicated on every shard.  The same session on a 1-shard mesh
produces the same bytes and the same head: shard count is an execution
detail, not a semantic one (DESIGN.md §5).
"""
import os

if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro import data as D
from repro.core import gmm as G
from repro.core import head as H
from repro.fl import api as FA
from repro.launch import compile_cache


def main():
    I, N, d, C = 8, 96, 16, 6
    dcfg = D.DatasetConfig(n_classes=C, n_per_class=I * N // C,
                           input_dim=d, class_sep=2.0)
    x, y = D.make_dataset(dcfg)
    x_test, y_test = D.make_dataset(dcfg, split=1)
    feats = x[: I * N].reshape(I, N, d)
    labels = y[: I * N].reshape(I, N)

    def session(shards):
        return FA.FedSession(
            n_classes=C,
            summarizer=FA.GMMSummarizer(
                G.GMMConfig(n_components=3, cov_type="diag", n_iter=15)),
            head=H.HeadConfig(n_steps=300, lr=3e-3),
            shards=shards)

    key = jax.random.PRNGKey(0)
    print(f"host devices: {jax.device_count()}")
    results = {}
    for n in (1, 8):
        # deliberate same-stream replay: shards=1 and shards=8 must see
        # identical keys so the weight comparison below isolates sharding
        res = session(n).run_sharded(key, feats, labels)  # lint: disable=KEY-CHAIN
        acc = float(H.accuracy(res.model, x_test, y_test))
        results[n] = res
        print(f"shards={n}:  comm={res.info['comm_bytes']:6d} B  "
              f"(Eqs. 9-11: {G.comm_bytes('diag', d, 3, C, 2) * I} B)   "
              f"test acc={acc:.3f}")
    w1 = np.asarray(results[1].model["w"])
    w8 = np.asarray(results[8].model["w"])
    print(f"max |head_1shard − head_8shard| = {np.abs(w1 - w8).max():.2e} "
          "— shard count is an execution detail.")
    return 0


if __name__ == "__main__":
    compile_cache.enable()
    main()
