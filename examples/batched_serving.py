"""Batched serving example: continuous batching over a reduced backbone.

    PYTHONPATH=src python examples/batched_serving.py
"""
import time

import jax

from repro.configs import get_config
from repro.launch import compile_cache
from repro.models import model as M
from repro.serve.server import BatchedServer, Request, ServerConfig


def main():
    cfg = get_config("granite-3-2b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    srv = BatchedServer(cfg, params, ServerConfig(n_slots=3, max_seq=96))

    prompts = [
        jax.random.randint(jax.random.PRNGKey(i), (4 + 3 * i,), 0,
                           cfg.vocab_size)
        for i in range(6)
    ]
    reqs = [Request(rid=i, prompt=p, max_new=8)
            for i, p in enumerate(prompts)]
    t0 = time.time()
    out = srv.run(reqs)
    dt = time.time() - t0
    total = sum(len(v) for v in out.values())
    print(f"served {len(reqs)} requests ({total} tokens) through "
          f"{srv.scfg.n_slots} slots in {dt:.1f}s")
    for rid in sorted(out):
        print(f"  req {rid} (prompt {len(prompts[rid]):2d} toks) → "
              f"{out[rid]}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
