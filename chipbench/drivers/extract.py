"""Extraction traffic through ``FedPFTService``: the closed loop.

Set-up makes the weights from the seed, builds the service the way a
deployment does, and runs one step at each prompt bucket the mix's groups
reach.  The window keeps ``queue_depth`` extraction requests queued and
calls ``step()`` until ``--seconds`` have passed; the rate counts every
real prompt token of the requests completed, over the whole window.  The
comparison takes a seeded sample of those requests, the longest among
them, and holds each served feature row against the plain float32
decoder.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from yardstick import harness as HN
from yardstick.program import program_config
from yardstick import reference as REF
from yardstick import traffic as TF
from yardstick import weights as W
from yardstick.tracing import span


def _check_layout(cfg, params):
    """The benchmark's weights have the program's layout and types."""
    import jax
    from repro.models import model as M
    want = jax.eval_shape(lambda k: M.init_params(cfg, k),
                          jax.random.PRNGKey(0))
    want.pop("lm_head")
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise ValueError(f"weights layout differs from init_params: "
                         f"{got} vs {want}")


def build_service(cell, params, cfg):
    from repro.fl import ingest as IG
    from repro.fl.api import FedSession
    from repro.serve.service import FedPFTService, ServiceConfig
    mix = cell.mix
    session = FedSession(n_classes=mix["n_classes"],
                         ingest=IG.IngestConfig())
    return FedPFTService(cfg, params, session, ServiceConfig(
        n_slots=mix["n_slots"], max_seq=mix["max_seq"],
        min_bucket=mix["min_bucket"]))


def observe_steps(svc, shapes: list):
    """Record the (rows, positions) of every feature-step call."""
    inner = svc._feats

    def observed(params, tokens, length):
        shapes.append(tuple(tokens.shape))
        return inner(params, tokens, length)
    svc._feats = observed


def setup(cell, seed, log):
    import jax
    mix, model = cell.mix, cell.model
    cfg = program_config(model)
    t0 = time.perf_counter()
    params = jax.block_until_ready(W.make_params(seed, model))
    log(f"weights: {time.perf_counter() - t0:.3f} s")
    _check_layout(cfg, params)
    svc = build_service(cell, params, cfg)
    shapes: list = []
    observe_steps(svc, shapes)
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


def window(state, seconds):
    svc, source, stream = state["svc"], state["source"], state["stream"]
    depth = state["mix"]["queue_depth"]
    sent = state["sent"]            # (request, offset, length)
    queued = 0
    steps = HN.StepLog()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with span("window"):
        while True:
            with steps.step():
                with span("submit"):
                    while queued < depth:
                        for n in next(stream):
                            off, toks = source.prompt(int(n))
                            sent.append((svc.submit_extract(toks), off,
                                         int(n)))
                            queued += 1
                with span("step"):
                    queued -= svc.step()
            if time.perf_counter() >= deadline:
                break
    t_end = time.perf_counter()
    done = [(r, o, n) for r, o, n in sent if r.done]
    admitted = [x for x in sent if x[0].t_admit > 0]
    tokens = sum(n for _, _, n in done)
    win_s = t_end - t0
    state["done"] = done
    step_s = steps.durations()
    return HN.Window(
        end_to_end={"extract_tokens_per_s": tokens / win_s},
        attempted=len(admitted),
        failed=len(admitted) - len(done),
        record={"window_s": win_s, "steps": len(step_s), "tokens": tokens,
                "step_log": steps,
                "note": f"{len(step_s)} steps, the longest "
                        f"{max(step_s):.4f} s, median "
                        f"{float(np.median(step_s)):.4f} s",
                "lengths": [n for _, _, n in done],
                "step_shapes": list(state["shapes"]),
                "model": state["model"]})


def sample(done, rows: int, seed: int):
    """``rows`` requests drawn from the seed, the longest among them."""
    rng = np.random.default_rng([seed, 2])
    longest = max(range(len(done)), key=lambda i: done[i][2])
    rest = [i for i in range(len(done)) if i != longest]
    pick = [longest] + list(rng.choice(rest, size=min(rows - 1, len(rest)),
                                       replace=False))
    return [done[i] for i in pick]


def release(state):
    """Drop the program's state so the reference has the chip's memory."""
    for k in ("svc", "params"):
        state.pop(k, None)
    gc.collect()


def check(state, cell, seed, log):
    mix, model = state["mix"], state["model"]
    picked = sample(state["done"], mix["check_rows"], seed)
    got = np.stack([np.asarray(r.feats, np.float32) for r, _, _ in picked])
    pool = state["source"].pool
    S = mix["max_seq"]
    tokens = np.zeros((len(picked), S), np.int32)
    lengths = np.array([n for _, _, n in picked], np.int32)
    for i, (_, off, n) in enumerate(picked):
        tokens[i, :n] = pool[off:off + n]
    release(state)
    t0 = time.perf_counter()
    want = REF.decoder_features(seed, model, tokens, lengths)
    err = REF.row_errors(got, want)
    log(f"reference: {len(picked)} rows (longest {lengths.max()}) in "
        f"{time.perf_counter() - t0:.3f} s; relative L2 error per row "
        f"{[float(e) for e in err]}")
    state["checked"] = {"tokens": tokens, "lengths": lengths, "want": want,
                        "err": err}
    return [HN.Check("feature_rel_err", float(np.max(err)),
                     float(cell.limits["feature_rel_err"]))]


def control(state, cell, seed, log):
    """The fp8 control on the rows ``check`` compared: its reading of each
    compared number, against the same float32 reference."""
    c = state["checked"]
    low = REF.decoder_features(seed, state["model"], c["tokens"],
                               c["lengths"], quant="fp8")
    err = REF.row_errors(low, c["want"])
    log(f"control (fp8): relative L2 error per row {[float(e) for e in err]}")
    return {"feature_rel_err": float(np.max(err))}
