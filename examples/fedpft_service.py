"""FedPFT-as-a-service: the paper's loop as one serving process.

    PYTHONPATH=src python examples/fedpft_service.py

One process, two traffic classes, one fixed slot pool (DESIGN.md §12):

1. clients stream raw samples in as **extraction** requests — the
   backbone mean-pools features under continuous batching (prompts
   bucket to power-of-two padded lengths, so compiles stay bounded);
2. each client fits per-class GMMs over ITS returned features and
   submits the wire message through the session's ingest broker
   (admission verdicts, byte accounting — DESIGN.md §9);
3. ``close_round`` trains the global head from the broker's reservoir
   through the warm AOT round-program cache (DESIGN.md §11) — the same
   head, bit for bit, the offline ``FedSession.run`` would produce;
4. the head opens for **inference** requests, interleaved with round-2
   extraction through the same slots.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import gmm as G
from repro.fl.api import FedSession, GMMSummarizer
from repro.fl.ingest import IngestConfig
from repro.launch import compile_cache
from repro.launch.aot_cache import ProgramCache
from repro.models import model as M
from repro.serve.service import FedPFTService, ServiceConfig


def main():
    cfg = dataclasses.replace(
        get_config("granite-3-2b").reduced(n_layers=1, d_model=64),
        dtype="float32", remat=False)
    params = M.init_params(cfg, jax.random.PRNGKey(3))
    n_classes, n_clients, n_per = 4, 6, 12

    session = FedSession(
        n_classes=n_classes,
        summarizer=GMMSummarizer(G.GMMConfig(2, "diag")),
        ingest=IngestConfig(capacity=32, chunk_size=8),
        program_cache=ProgramCache())
    svc = FedPFTService(cfg, params, session,
                        ServiceConfig(n_slots=8, max_seq=32))
    print("warmup:", svc.warmup(d=cfg.d_model))

    # -- round 1: extraction traffic --------------------------------------
    rng = np.random.default_rng(0)
    reqs = {c: [svc.submit_extract(rng.integers(
        1, cfg.vocab_size, size=int(rng.integers(3, 30))))
        for _ in range(n_per)] for c in range(n_clients)}
    svc.drain()

    # -- clients summarize and submit through the broker ------------------
    key = jax.random.PRNGKey(7)
    keys = jax.random.split(key, n_clients + 1)
    for c in range(n_clients):
        feats = jnp.stack([jnp.asarray(r.feats) for r in reqs[c]])
        labels = jnp.asarray(rng.integers(0, n_classes, size=n_per))
        msg = session.client_update(keys[1 + c], feats, labels, c)
        print(f"client {c}: {msg.comm_bytes}B ->",
              svc.submit_update(c, msg))

    # -- close the round: train + install the served head -----------------
    result = svc.close_round(keys[0])
    print("round closed, compile info:", result.info["compile"]["hit"],
          "(hit=True: the warm cache served the round program)")

    # -- round 2: interleaved extract + infer ------------------------------
    infer = [svc.submit_infer(rng.integers(1, cfg.vocab_size, size=7))
             for _ in range(8)]
    extract = [svc.submit_extract(rng.integers(1, cfg.vocab_size, size=9))
               for _ in range(8)]
    svc.drain()
    print("inferred labels:", [r.label for r in infer])
    print("round-2 features:", len([r for r in extract if r.done]))
    for kind, row in svc.stats().items():
        print(f"stats[{kind}]: {row}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
