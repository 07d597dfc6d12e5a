"""Extraction traffic through ``FedPFTService`` on the Mamba-2/attention
hybrid: ``extract.py``'s closed loop over granite-4.0-h.

The window, the service, the record of step shapes, the seeded sample of
completed requests and the release of the program's memory are
``extract.py``'s own.  What differs is written here: set-up makes the
hybrid's weights from the seed (``yardstick/hybrid.py``) and checks their
layout against ``init_params``; the comparison and the fp8 control run
the hybrid's plain float32 reference.  The feature step takes one request
per row: the family does not pack.
"""
from __future__ import annotations

import pathlib
import time

import numpy as np

from yardstick import harness as HN
from yardstick import hybrid as HY
from yardstick import reference as REF
from yardstick import traffic as TF

_EX = HN._load_file(pathlib.Path(__file__).with_name("extract.py"),
                    "driver")
window = _EX.window


def setup(cell, seed, log):
    import jax
    mix, model = cell.mix, cell.model
    cfg = HY.program_config(model)
    t0 = time.perf_counter()
    params = jax.block_until_ready(HY.make_params(seed, model))
    log(f"weights: {time.perf_counter() - t0:.3f} s")
    _EX._check_layout(cfg, params)
    svc = _EX.build_service(cell, params, cfg)
    shapes: list = []
    _EX.observe_steps(svc, shapes)
    rng = np.random.default_rng([seed, 1])
    source = TF.PromptSource(rng, model["vocab_size"])
    groups = TF.design_groups(mix["lengths"], mix["n_slots"])
    buckets = TF.group_buckets(groups, mix["min_bucket"], mix["max_seq"])
    t0 = time.perf_counter()
    for b in buckets + buckets[-1:]:
        for _ in range(mix["n_slots"]):
            svc.submit_extract(source.prompt(b)[1])
        svc.step()
    log(f"warm-up of buckets {buckets}: {time.perf_counter() - t0:.3f} s")
    shapes.clear()
    return {"svc": svc, "params": params, "cfg": cfg, "source": source,
            "stream": TF.GroupStream(groups, rng, mix["block"]),
            "shapes": shapes,
            "mix": mix, "model": model, "sent": []}


def check(state, cell, seed, log):
    mix, model = state["mix"], state["model"]
    picked = _EX.sample(state["done"], mix["check_rows"], seed)
    got = np.stack([np.asarray(r.feats, np.float32) for r, _, _ in picked])
    pool = state["source"].pool
    tokens = np.zeros((len(picked), mix["max_seq"]), np.int32)
    lengths = np.array([n for _, _, n in picked], np.int32)
    for i, (_, off, n) in enumerate(picked):
        tokens[i, :n] = pool[off:off + n]
    _EX.release(state)
    t0 = time.perf_counter()
    want = HY.features(seed, model, tokens, lengths)
    err = REF.row_errors(got, want)
    log(f"reference: {len(picked)} rows (longest {lengths.max()}) in "
        f"{time.perf_counter() - t0:.3f} s; relative L2 error per row "
        f"{[float(e) for e in err]}")
    state["checked"] = {"tokens": tokens, "lengths": lengths, "want": want}
    return [HN.Check("feature_rel_err", float(np.max(err)),
                     float(cell.limits["feature_rel_err"]))]


def control(state, cell, seed, log):
    """The fp8 control on the rows ``check`` compared, against the same
    float32 reference."""
    c = state["checked"]
    low = HY.features(seed, state["model"], c["tokens"], c["lengths"],
                      quant="fp8")
    err = REF.row_errors(low, c["want"])
    log(f"control (fp8): relative L2 error per row {[float(e) for e in err]}")
    return {"feature_rel_err": float(np.max(err))}
