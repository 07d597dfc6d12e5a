"""Federation rounds through ``FedPFTService``, with the backbone bypassed.

Set-up makes the cohort on the device from the seed: ``n_clients`` clients
of ``n_samples`` features each at the configuration's width, class counts
drawn once with Dirichlet(``alpha``) proportions from ``design_seed`` and
dealt by the seed to clients and class names, each class a mixture of
``true_components`` Gaussians around a shared offset (real features have
a large common mean, which is what makes EM's moments cancel).  It
pre-compiles the round program (``warmup``) and runs one whole round.

The window runs rounds until ``--seconds`` have passed: every client's
``client_update`` → ``submit_update``, then ``close_round`` with the head
installed; each round with fresh keys.  ``round_s`` is the window over
the rounds completed.

The comparison covers the last round of the window: the reservoir the
server holds after the seal against a plain decode of every admitted
payload's bytes (exact), each client's fit against one reference EM step,
and the served head against a plain float32 server phase trained from
those decoded payloads with the round's own head key: each step's loss,
and the logits of held-out rows of the true distribution.
"""
from __future__ import annotations

import time

import numpy as np

from yardstick import fedref as FR
from yardstick import harness as HN
from yardstick import weights as W
from yardstick.program import program_config
from yardstick.tracing import span


def cohort(seed: int, t: dict, d: int):
    """(features (I, N, d) on device, labels (I, N), held-out rows), from
    the seed."""
    import jax
    import jax.numpy as jnp
    I, N, C, J = t["n_clients"], t["n_samples"], t["n_classes"], \
        t["true_components"]
    design = np.random.default_rng(t["design_seed"])
    counts = np.stack([design.multinomial(N, design.dirichlet(
        [t["alpha"]] * C)) for _ in range(I)])
    # every seed deals the same class counts: to other clients, under
    # other class names, so the work of a round does not depend on it
    rng = np.random.default_rng([seed, 3])
    counts = counts[rng.permutation(I)][:, rng.permutation(C)]
    labels = np.stack([rng.permutation(np.repeat(np.arange(C), c))
                       for c in counts]).astype(np.int32)
    comps = rng.integers(0, J, (I, N)).astype(np.int32)
    M = t["held_out"]
    h_labels = rng.integers(0, C, M).astype(np.int32)
    h_comps = rng.integers(0, J, M).astype(np.int32)
    g = t["generator"]

    @jax.jit
    def make(key, lab, cmp, hl, hc):
        k = jax.random.split(key, 6)
        offset = g["offset"] * jax.random.normal(k[0], (d,))
        means = offset + g["class"] * jax.random.normal(k[1], (C, d))
        comp = means[:, None] + g["component"] * jax.random.normal(
            k[2], (C, J, d))
        sd = g["spread"] * jnp.exp(g["spread_log_sd"] * jax.random.normal(
            k[3], (C, J, d)))
        x = comp[lab, cmp] + sd[lab, cmp] * jax.random.normal(
            k[4], lab.shape + (d,))
        h = comp[hl, hc] + sd[hl, hc] * jax.random.normal(k[5], hl.shape
                                                          + (d,))
        return x, h

    x, held = make(jax.random.fold_in(W.root_key(seed), 7), labels, comps,
                   h_labels, h_comps)
    return x, labels, held


def setup(cell, seed, log):
    import jax
    import jax.numpy as jnp
    from repro.core import gmm as G
    from repro.core import head as H
    from repro.fl import ingest as IG
    from repro.fl import round as RD
    from repro.fl.api import FedSession, GMMSummarizer
    from repro.launch.aot_cache import ProgramCache
    from repro.serve.service import FedPFTService, ServiceConfig

    t, model = cell.mix, cell.model
    cfg = program_config(model)
    d = model["hidden_size"]
    t0 = time.perf_counter()
    x, labels, held = cohort(seed, t, d)
    x = jax.block_until_ready(x)
    feats = [x[i] for i in range(t["n_clients"])]
    labs = [jnp.asarray(labels[i]) for i in range(t["n_clients"])]
    log(f"cohort {x.shape}: {time.perf_counter() - t0:.3f} s")
    gcfg = G.GMMConfig(n_components=t["n_components"], cov_type="diag",
                       n_iter=t["em_iters"], kmeans_iter=t["kmeans_iters"],
                       reg=t["reg"])
    session = FedSession(
        n_classes=t["n_classes"], summarizer=GMMSummarizer(gcfg),
        head=H.HeadConfig(n_steps=t["head"]["n_steps"],
                          batch_size=t["head"]["batch_size"],
                          lr=t["head"]["lr"],
                          noise_window=t["head"]["noise_window"]),
        ingest=IG.IngestConfig(capacity=RD.next_pow2(
            t["n_clients"] * t["n_classes"])),
        program_cache=ProgramCache())
    # the backbone is bypassed: the service holds no weights
    svc = FedPFTService(cfg, None, session, ServiceConfig())
    t0 = time.perf_counter()
    warm = svc.warmup(d=d)
    log(f"round program warm-up {warm}: {time.perf_counter() - t0:.3f} s")
    state = {"svc": svc, "session": session, "feats": feats, "labs": labs,
             "x": x, "labels": labels, "held": held,
             "key": W.root_key(seed), "t": t, "model": model, "gcfg": gcfg}
    t0 = time.perf_counter()
    one_round(state, 0)
    log(f"warm-up round: {time.perf_counter() - t0:.3f} s")
    return state


def one_round(state, r: int, spans=None):
    """Every client's update and submission, then ``close_round``; hands
    back what the check reads: the messages, the sealed broker, the head,
    its loss trace and the round's key."""
    import jax
    svc, session = state["svc"], state["session"]
    key = jax.random.fold_in(state["key"], 1000 + r)
    k_clients, k_round = jax.random.split(key)
    msgs, verdicts = [], []
    for i, (f, lab) in enumerate(zip(state["feats"], state["labs"])):
        t0 = time.perf_counter()
        with span("client_update"):
            msg = session.client_update(jax.random.fold_in(k_clients, i),
                                        f, lab, i)
        t1 = time.perf_counter()
        with span("submit_update"):
            verdicts.append(svc.submit_update(i, msg))
        t2 = time.perf_counter()
        if spans is not None:
            spans["client_update"].append(t1 - t0)
            spans["submit_update"].append(t2 - t1)
        msgs.append(msg)
    broker = svc.broker
    t0 = time.perf_counter()
    with span("close_round"):
        res = svc.close_round(k_round)
        jax.block_until_ready(svc.head)
    if spans is not None:
        spans["close_round"].append(time.perf_counter() - t0)
    return {"msgs": msgs, "verdicts": verdicts, "head": svc.head,
            "losses": res.info.get("head_losses"), "broker": broker,
            "key": k_round}


def window(state, seconds):
    spans = {"client_update": [], "submit_update": [], "close_round": []}
    rounds = admitted = 0
    steps = HN.StepLog()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with span("window"):
        while True:
            rounds += 1
            with steps.step(), span("round"):
                out = one_round(state, rounds, spans=spans)
            admitted += sum(v == "admitted" for v in out["verdicts"])
            if time.perf_counter() >= deadline:
                break
            # a deployment drops a round's messages, result and sealed
            # broker at once; holding them into the next round changes what
            # its close_round costs (0.14 s against 0.37 s on a TPU v5e host)
            del out
    win_s = time.perf_counter() - t0
    state["last"] = out
    t = state["t"]
    attempted = rounds * t["n_clients"]
    round_s = steps.durations()
    return HN.Window(
        end_to_end={"round_s": win_s / rounds},
        attempted=attempted, failed=attempted - admitted,
        record={"window_s": win_s, "rounds": rounds, "spans": spans,
                "step_log": steps,
                "note": f"{rounds} rounds, the longest {max(round_s):.4f} s,"
                        f" median {float(np.median(round_s)):.4f} s",
                "t": t, "model": state["model"],
                "class_rows": [np.bincount(state["labels"][i],
                                           minlength=t["n_classes"])
                               .tolist() for i in range(t["n_clients"])]})


def release(state):
    for k in ("svc", "session", "feats", "labs"):
        state.pop(k, None)


def check(state, cell, seed, log):
    import jax
    import jax.numpy as jnp
    t, gcfg = state["t"], state["gcfg"]
    C, hc = t["n_classes"], t["head"]
    out = state["last"]
    head = jax.device_get((out["head"]["w"], out["head"]["b"]))
    losses = np.zeros(0) if out["losses"] is None else np.asarray(
        jax.device_get(out["losses"]))
    # what the server holds after the seal (closing a sealed broker again
    # hands back its state)
    r = out["broker"].close()
    held = None if r is None else {"ids": r.slot_ids, "counts": r.counts,
                                   "pi": r.pi, "mu": r.mu, "var": r.cov}
    release(state)
    t0 = time.perf_counter()
    want = FR.slot_stack(out["msgs"], C)
    rows = FR.rows_off(held, want)
    # the service trains its head with the second half of the round key
    ref, ref_loss = FR.server_head(
        jax.random.split(out["key"])[1], jnp.asarray(want["counts"]),
        jnp.asarray(want["pi"]), jnp.asarray(want["mu"]),
        jnp.asarray(want["var"]), jnp.asarray(want["ids"] % C),
        n_classes=C, steps=hc["n_steps"], batch=hc["batch_size"],
        lr=hc["lr"], window=hc["noise_window"])
    head_gap = FR.logit_gap(head, ref, state["held"])
    loss_gap = FR.loss_gap(losses, ref_loss)
    gaps = {"em_mu_gap": 0.0, "em_var_gap": 0.0, "em_pi_gap": 0.0}
    for i, msg in enumerate(out["msgs"]):
        h = msg.header
        pi, mu, var = FR.decode(msg.payload, h.counts, h.K, h.d,
                                jnp.dtype(h.dtype))
        g = FR.fit_gaps(state["x"][i], state["labels"][i], pi, mu, var,
                        gcfg.reg)
        for name, v in g.items():
            gaps[name] = max(gaps[name], v)
    log(f"reference: the last round's {t['n_clients']} clients in "
        f"{time.perf_counter() - t0:.3f} s; gaps {gaps}; reservoir rows "
        f"off {rows}; head logit gap {head_gap}; head loss gap {loss_gap}")
    lim = cell.limits
    # μ's and π's gaps are logged, not compared: a first moment has no
    # cancellation, so a bf16 fit moves them no further than the wire's
    # own rounding, and no limit separates the control from the program
    return [HN.Check("reservoir_rows_off", float(rows),
                     lim["reservoir_rows_off"]),
            HN.Check("em_var_gap", gaps["em_var_gap"], lim["em_var_gap"]),
            HN.Check("head_loss_gap", loss_gap, lim["head_loss_gap"]),
            HN.Check("head_gap", head_gap, lim["head_gap"])]


def control(state, cell, seed, log):
    """The reference fit at one bfloat16 pass per product (``em_fit`` with
    ``low``), in the program's place, read by the same numbers."""
    import jax.numpy as jnp
    t, gcfg = state["t"], state["gcfg"]
    x_all = state["x"]
    gaps = {"em_mu_gap": 0.0, "em_var_gap": 0.0, "em_pi_gap": 0.0}
    for i in range(t["n_clients"]):
        lab = state["labels"][i]
        C, K, d = t["n_classes"], gcfg.n_components, x_all.shape[-1]
        pi = np.zeros((C, K), np.float32)
        mu = np.zeros((C, K, d), np.float32)
        var = np.ones((C, K, d), np.float32)
        for c in range(C):
            w = (lab == c).astype(np.float32)
            if w.sum() == 0:
                continue
            p, m, v = em_low(x_all[i], jnp.asarray(w), gcfg)
            pi[c], mu[c], var[c] = (np.asarray(a) for a in (p, m, v))
        g = FR.fit_gaps(x_all[i], lab, pi, mu, var, gcfg.reg)
        for k, v in g.items():
            gaps[k] = max(gaps[k], v)
    log(f"control (bf16 EM): gaps {gaps}")
    return gaps


def em_low(x, w, gcfg):
    return FR.em_fit(x, w, K=gcfg.n_components, iters=gcfg.n_iter,
                     kmeans_iters=gcfg.kmeans_iter, reg=gcfg.reg, low=True)
