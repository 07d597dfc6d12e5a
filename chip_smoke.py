"""Chip smoke test: one FedPFT service round at granite-3-2b's full width.

    python chip_smoke.py                # one TPU: the service round
    python chip_smoke.py --four-chips   # four TPUs: the mesh round vs host

The default run drives the paper's loop once, in one process, through the
entry points a deployment calls (DESIGN.md §12): ``FedPFTService``
extracts features with granite-3-2b (40 layers, d_model 2048, bf16,
random weights from ``--seed``), each client fits classwise diag GMMs
with ``FedSession.client_update`` and submits through the broker,
``close_round`` trains the head from the warm AOT round-program cache, and
inference requests are classified against it.  Three checks compare the
results with references:

  (a) served features = ``model.features`` on the unpadded prompts;
  (b) the compiled fused E-step at the fit's own shapes = the XLA
      reference at full f32 matmul precision;
  (c) the served head = ``FedSession.aggregate_from_broker`` run offline
      on the same admitted cohort, bit for bit.

``--four-chips`` runs only the mesh round (``FedSession(mesh=...)``:
client EM sharded over ``data``, the bf16 wire all-gathered) and the same
cohort through the host path, and compares the two.

Every check prints the numbers it compared.  Timings are smoke timings
for bring-up, not benchmark metrics.  The script exits non-zero, without
the result line, when JAX's default device is not a TPU or any phase or
check fails.  Its last line is the result:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time
from functools import partial
from typing import List, Optional

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

MODEL = "granite-3-2b"


@dataclasses.dataclass(frozen=True)
class Size:
    """One service round's shape.  ``n_layers``/``d_model`` None keep the
    published config; tests pass a cut-down size to rehearse on the CPU."""
    n_layers: Optional[int] = None
    d_model: Optional[int] = None
    n_clients: int = 8
    n_prompts: int = 32          # extraction requests per client
    min_len: int = 16
    max_len: int = 256
    n_slots: int = 16
    n_classes: int = 4
    n_components: int = 2
    n_probe: int = 4             # rows checked against model.features
    probe_len: int = 77          # not a bucket length: the rows are padded
    n_infer: int = 16


@dataclasses.dataclass(frozen=True)
class MeshSize:
    """The four-chip cohort: clients × samples of seeded d-dim features."""
    n_devices: int = 4
    n_clients: int = 16
    n_samples: int = 256
    d: int = 2048
    n_classes: int = 4
    n_components: int = 2


class Report:
    """Prints each check and timing on its own line; remembers failures."""

    def __init__(self):
        self.failed: List[str] = []

    def check(self, name: str, ok: bool, detail: str) -> bool:
        print(f"check {name}: {'PASS' if ok else 'FAIL'} — {detail}",
              flush=True)
        if not ok:
            self.failed.append(name)
        return ok

    def timing(self, name: str, seconds: float, note: str = "") -> None:
        extra = f" ({note})" if note else ""
        print(f"smoke timing, not a benchmark metric: {name} = "
              f"{seconds:.3f} s{extra}", flush=True)


def _block(tree):
    import jax
    return jax.block_until_ready(tree)


# ---------------------------------------------------------------------------
# one chip: the service round
# ---------------------------------------------------------------------------


def model_config(size: Size):
    from repro.configs import get_config
    cfg = get_config(MODEL)
    if size.n_layers is None and size.d_model is None:
        return cfg
    small = cfg.reduced(n_layers=size.n_layers or cfg.n_layers,
                        d_model=size.d_model or cfg.d_model)
    return dataclasses.replace(small, remat=False)


def service_round(report: Report, size: Size = Size(), seed: int = 0):
    """Extract → fit → broker → close_round → infer, with checks (a)–(c)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import gmm as G
    from repro.fl import ingest as IG
    from repro.fl import round as FR
    from repro.fl.api import FedSession, GMMSummarizer
    from repro.kernels import ops, ref
    from repro.launch.aot_cache import ProgramCache
    from repro.models import model as M
    from repro.serve.service import FedPFTService, ServiceConfig

    cfg = model_config(size)
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype}", flush=True)
    t0 = time.perf_counter()
    params = _block(jax.jit(partial(M.init_params, cfg))(
        jax.random.PRNGKey(seed)))
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    report.timing("init params on device (compile + run)",
                  time.perf_counter() - t0, f"{n_params} parameters")

    C, K = size.n_classes, size.n_components
    session = FedSession(
        n_classes=C, summarizer=GMMSummarizer(G.GMMConfig(K, "diag")),
        ingest=IG.IngestConfig(capacity=FR.next_pow2(size.n_clients * C),
                               chunk_size=size.n_clients),
        program_cache=ProgramCache())
    svc = FedPFTService(cfg, params, session,
                        ServiceConfig(n_slots=size.n_slots,
                                      max_seq=size.max_len))
    t0 = time.perf_counter()
    warm = svc.warmup(d=cfg.d_model)
    report.timing("round program AOT compile (warmup)",
                  time.perf_counter() - t0)
    cache = session.program_cache
    warm_ok = warm["compiles"] == 1 and warm["jit_fallbacks"] == 0
    report.check("warmup", warm_ok, f"cache after warmup {warm}")

    # -- extraction traffic ------------------------------------------------
    rng = np.random.default_rng(seed)

    def prompt(n):
        return rng.integers(1, cfg.vocab_size, size=n, dtype=np.int32)

    reqs = {}
    probes = []
    for c in range(size.n_clients):
        reqs[c] = [svc.submit_extract(prompt(int(rng.integers(
            size.min_len, size.max_len + 1)))) for _ in range(size.n_prompts)]
        if c == size.n_clients // 2:        # probes share steps with clients
            probes = [svc.submit_extract(prompt(size.probe_len))
                      for _ in range(size.n_probe)]
    t0 = time.perf_counter()
    n_done = svc.drain()
    dt = time.perf_counter() - t0
    report.timing("extraction drain (includes feature-step compiles)", dt,
                  f"{n_done} requests in {svc.steps} steps, "
                  f"{svc.feature_compiles()} feature-step compiles")
    done_ok = all(r.done and r.feats is not None
                  and r.feats.shape == (cfg.d_model,)
                  and np.isfinite(r.feats).all()
                  for rs in list(reqs.values()) + [probes] for r in rs)
    report.check("extract", done_ok,
                 f"{n_done} requests done, each a finite ({cfg.d_model},) "
                 "feature")

    # service step time at the largest bucket, compiled already
    step_s = []
    for _ in range(3):
        for _ in range(size.n_slots):
            svc.submit_extract(prompt(size.max_len))
        t0 = time.perf_counter()
        svc.step()                           # ends in device_get: synced
        step_s.append(time.perf_counter() - t0)
    report.timing(f"service step ({size.n_slots} slots × {size.max_len} "
                  "tokens), median of 3", float(np.median(step_s)),
                  "includes host batching and device_get")

    # (a) served features vs model.features on the unpadded prompts
    toks = jnp.asarray(np.stack([r.tokens for r in probes]))
    want = np.asarray(jax.jit(partial(M.features, cfg))(
        params, {"tokens": toks}), np.float32)
    got = np.stack([r.feats for r in probes]).astype(np.float32)
    rel = (np.linalg.norm(got - want, axis=-1)
           / np.maximum(np.linalg.norm(want, axis=-1), 1e-30))
    # bf16 activations round at 2^-9 relative; the padded service batch
    # and the unpadded reference lower to differently tiled programs, so
    # each layer may round differently.  5e-2 leaves room for that drift
    # through the stack and still catches a masking fault, which moves a
    # padded row by O(1).
    tol_a = 5e-2
    report.check("a_features", bool((rel <= tol_a).all()),
                 f"{len(probes)} rows of length {size.probe_len}, padded in "
                 f"service steps: relative L2 error per row "
                 f"{[float(x) for x in rel]} vs tolerance {tol_a}")

    # -- clients: fit classwise GMMs, submit through the broker ------------
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), size.n_clients + 1)
    round_key = keys[0]
    labels = {c: rng.integers(0, C, size=size.n_prompts).astype(np.int32)
              for c in range(size.n_clients)}
    feats = {c: np.stack([r.feats for r in reqs[c]]).astype(np.float32)
             for c in range(size.n_clients)}
    msgs, verdicts, fit_s = {}, {}, []
    for c in range(size.n_clients):
        t0 = time.perf_counter()
        msgs[c] = session.client_update(keys[1 + c], jnp.asarray(feats[c]),
                                        jnp.asarray(labels[c]), c)
        fit_s.append(time.perf_counter() - t0)
        verdicts[c] = svc.submit_update(c, msgs[c])
    report.timing("first client fit + encode (includes EM compile)",
                  fit_s[0])
    report.timing("client fit + encode, median of the rest",
                  float(np.median(fit_s[1:])),
                  f"N={size.n_prompts}, C={C}, K={K}, d={cfg.d_model}")
    report.check("admitted", all(v == IG.ADMITTED
                                 for v in verdicts.values()),
                 f"broker verdicts {verdicts}")

    # the E-step dispatch the fit ran, and (b) the kernel vs the reference
    want_mode = ops.PALLAS if jax.default_backend() == "tpu" \
        else ops.INTERPRET
    fit_cfg = session.summarizer.gmm
    fit_text = jax.jit(G.fit_classwise_gmms, static_argnums=(3, 4)).lower(
        keys[1], jnp.asarray(feats[0]), jnp.asarray(labels[0]), C,
        fit_cfg).as_text()
    has_kernel = "tpu_custom_call" in fit_text
    report.check("em_dispatch", ops.backend() == want_mode
                 and has_kernel == (want_mode == ops.PALLAS),
                 f"ops.backend()={ops.backend()!r} (want {want_mode!r}); "
                 f"Mosaic kernel in the client fit's program: {has_kernel}")
    k_fit, _ = jax.random.split(keys[1])
    gmms, _, _ = session.summarizer.summarize(
        k_fit, jnp.asarray(feats[0]), jnp.asarray(labels[0]), C)
    x = jnp.asarray(feats[0])[None]                  # (1, N, d), shared
    lp, lse = _block(ops.gmm_estep_fused(x, gmms["mu"], gmms["cov"],
                                         gmms["pi"]))
    with jax.default_matmul_precision("highest"):
        lp_ref, lse_ref = _block(jax.jit(ref.estep_fused_ref)(
            x, gmms["mu"], gmms["cov"], gmms["pi"]))
    inv = 1.0 / np.asarray(gmms["cov"], np.float64)
    mu64 = np.asarray(gmms["mu"], np.float64)
    x64 = np.asarray(feats[0], np.float64)
    # the expansion cancels Σx²/σ² against 2Σxμ/σ² and Σμ²/σ², so f32
    # rounding scales with these sums, not with the log-density itself
    scale = float(np.max(np.einsum("nd,ckd->cnk", x64 ** 2, inv)
                         + np.sum(mu64 ** 2 * inv, -1)[:, None, :]))
    err_lp = float(np.max(np.abs(np.asarray(lp) - np.asarray(lp_ref))))
    err_lse = float(np.max(np.abs(np.asarray(lse) - np.asarray(lse_ref))))
    # f32 accumulation over d terms leaves ~√d·2^-24 ≈ 2.7e-6 of the scale
    # at d=2048; a bf16 matmul pass would leave ~2^-8/√d ≈ 8.6e-5
    tol_b = 2e-5 * scale
    report.check("b_estep", err_lp <= tol_b and err_lse <= tol_b,
                 f"fit shapes x{tuple(x.shape)} mu{tuple(gmms['mu'].shape)}:"
                 f" max|Δlogp|={err_lp:.6g}, max|Δlse|={err_lse:.6g} vs "
                 f"tolerance {tol_b:.6g} (2e-5 × scale {scale:.6g}); "
                 f"max|logp|={float(np.max(np.abs(np.asarray(lp_ref)))):.6g}")

    # -- close the round: the warm round program trains the served head ---
    before = cache.snapshot()
    t0 = time.perf_counter()
    res = svc.close_round(round_key)
    report.timing("close_round (warm round program)",
                  time.perf_counter() - t0)
    st, moved = cache.stats(), cache.delta(before)
    comp = res.info.get("compile", {})     # absent for an empty cohort
    report.check("close_round",
                 comp.get("hit") is True and moved["compiles"] == 0
                 and st["jit_fallbacks"] == 0 and st["cost_failures"] == 0
                 and st["serialize_failures"] == 0,
                 f"hit={comp.get('hit')} aot={comp.get('aot')}, new "
                 f"compiles={moved['compiles']}, cache {st}")
    failures = cache.failures()
    report.check("cache_errors", not failures,
                 f"entries with failed phases: {failures}")

    # (c) the served head vs the offline session on the admitted cohort
    offline_broker = IG.IngestBroker(session.ingest, C,
                                     samples_per_class=session
                                     .samples_per_class)
    for c in sorted(msgs):
        offline_broker.submit(c, msgs[c])
    offline = session.aggregate_from_broker(round_key, offline_broker)
    diffs = {p: float(np.max(np.abs(np.asarray(svc.head[p])
                                    - np.asarray(offline.model[p]))))
             for p in ("w", "b")}
    same = all(np.array_equal(np.asarray(svc.head[p]),
                              np.asarray(offline.model[p]))
               for p in ("w", "b"))
    report.check("c_head", same,
                 f"served vs offline head bit-identical: {same}; "
                 f"max|Δ| {diffs}; head shapes "
                 f"{ {p: tuple(svc.head[p].shape) for p in ('w', 'b')} }")

    # -- inference against the served head ---------------------------------
    infer = [svc.submit_infer(prompt(int(rng.integers(size.min_len,
                                                      size.max_len + 1))))
             for _ in range(size.n_infer)]
    t0 = time.perf_counter()
    svc.drain()
    report.timing("inference drain", time.perf_counter() - t0,
                  f"{len(infer)} requests")
    got_labels = [r.label for r in infer]
    report.check("infer", all(r.done and r.label is not None
                              and 0 <= r.label < C for r in infer),
                 f"labels {got_labels}")
    return svc


# ---------------------------------------------------------------------------
# four chips: the mesh round against the host round
# ---------------------------------------------------------------------------


def mesh_round(report: Report, size: MeshSize = MeshSize(), seed: int = 0):
    """The same seeded cohort through ``FedSession(mesh=...)`` and the host
    path; compares wire bytes, decoded wire and heads."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import gmm as G
    from repro.core import head as H
    from repro.fl import api as FA
    from repro.launch.aot_cache import ProgramCache
    from repro.launch.mesh import make_sim_mesh

    mesh = make_sim_mesh(size.n_devices)
    I, N, d = size.n_clients, size.n_samples, size.d
    C, K = size.n_classes, size.n_components
    rng = np.random.default_rng(seed)
    # each class is K separated clusters, so each fit has one optimum: EM's
    # split of a unimodal class is arbitrary, and the two programs' rounding
    # differences pick different splits of it (the chip showed 7 of 16)
    centers = rng.normal(0.0, 1.0, (C, K, d)).astype(np.float32)
    labels = rng.integers(0, C, (I, N)).astype(np.int32)
    comps = rng.integers(0, K, (I, N))
    feats = (centers[labels, comps]
             + rng.normal(0.0, 0.5, (I, N, d)).astype(np.float32))
    shard = NamedSharding(mesh, P("data"))
    feats_d = jax.device_put(feats, shard)
    labels_d = jax.device_put(labels, shard)
    placed = [(s.index[0].start, str(s.device))
              for s in feats_d.addressable_shards]
    for start, dev in sorted(placed, key=lambda p: p[0] or 0):
        print(f"shard of clients from {start or 0}: {dev}", flush=True)
    n_distinct = len({dev for _, dev in placed})
    report.check("placement", n_distinct == size.n_devices,
                 f"{len(placed)} shards on {n_distinct} distinct devices, "
                 f"want {size.n_devices}")

    gcfg = G.GMMConfig(K, "diag")
    key = jax.random.PRNGKey(seed + 1)
    mesh_sess = FA.FedSession(n_classes=C, summarizer=FA.GMMSummarizer(gcfg),
                              mesh=mesh, program_cache=ProgramCache())
    t0 = time.perf_counter()
    mres = mesh_sess.run_sharded(key, feats_d, labels_d)
    _block(mres.model)
    report.timing("mesh round (compile + run)", time.perf_counter() - t0,
                  f"I={I} clients × N={N} samples, d={d}, C={C}, K={K}")
    head_devs = sorted(str(dv) for dv in mres.model["w"].sharding.device_set)
    print(f"mesh head replicated on: {head_devs}", flush=True)

    # host mode: the same clients, each fit with the mesh round's client
    # key PRNGKey(transfer_seed + i), encoded and aggregated off the mesh
    host_sess = FA.FedSession(n_classes=C, summarizer=FA.GMMSummarizer(gcfg),
                              program_cache=ProgramCache())
    t0 = time.perf_counter()
    host_msgs = []
    for i in range(I):
        gmms, counts, lls = host_sess.summarizer.summarize(
            jax.random.PRNGKey(host_sess.transfer_seed + i),
            jnp.asarray(feats[i]), jnp.asarray(labels[i]), C)
        host_msgs.append(FA.encode_message(
            gmms, counts, lls, kind="gmm", cov_type=gcfg.cov_type,
            n_classes=C, codec=host_sess.codec))
    hres = host_sess.server_aggregate(key, host_msgs)
    _block(hres.model)
    report.timing("host round (compile + run)", time.perf_counter() - t0)

    payload = sum(len(m.payload) for m in host_msgs)
    report.check("wire_bytes",
                 mres.info["comm_bytes"] == hres.info["comm_bytes"]
                 == payload and [len(m.payload) for m in mres.messages]
                 == [len(m.payload) for m in host_msgs],
                 f"mesh comm_bytes={mres.info['comm_bytes']}, host "
                 f"comm_bytes={hres.info['comm_bytes']}, host payloads "
                 f"{payload} B; all-gathered pytree "
                 f"{mres.info['mesh_wire_bytes']} B")
    same_payloads = sum(a.payload == b.payload
                        for a, b in zip(mres.messages, host_msgs))
    pairs = [(np.asarray(a.params[f], np.float32),
              np.asarray(b.params[f], np.float32))
             for a, b in zip(mres.messages, host_msgs)
             for f in G.WIRE_FIELDS]
    wire_err = max(float(np.max(np.abs(a - b))) for a, b in pairs)
    # the mesh fits (I/shards)·C fits per program, the host C: the two
    # programs may round differently, and a bf16 wire leaf then moves by a
    # whole ulp, up to 2^-7 of its value
    rtol, atol = 2.0 ** -7, 1e-6
    wire_ratio = max(float(np.max(np.abs(a - b) / (atol + rtol * np.abs(b))))
                     for a, b in pairs)
    report.check("wire_content", wire_ratio <= 1.0,
                 f"{same_payloads}/{I} client payloads byte-identical; "
                 f"decoded pi/mu/cov max|Δ| {wire_err:.6g}, max |Δ| / "
                 f"({atol} + {rtol}·|host|) = {wire_ratio:.6g} vs 1")
    head_err = {p: float(np.max(np.abs(np.asarray(mres.model[p])
                                       - np.asarray(hres.model[p]))))
                for p in ("w", "b")}
    flat = jnp.asarray(feats.reshape(-1, d))
    agree = float(np.mean(
        np.argmax(np.asarray(H.head_logits(mres.model, flat)), -1)
        == np.argmax(np.asarray(H.head_logits(hres.model, flat)), -1)))
    heads_ok = all(np.allclose(np.asarray(mres.model[p]),
                               np.asarray(hres.model[p]),
                               rtol=1e-2, atol=2e-2) for p in ("w", "b"))
    report.check("heads", heads_ok and agree >= 0.98,
                 f"max|Δ| {head_err} (rtol 1e-2, atol 2e-2); argmax "
                 f"agreement on the cohort's {flat.shape[0]} rows {agree}")
    for name, sess in (("mesh", mesh_sess), ("host", host_sess)):
        st = sess.program_cache.stats()
        report.check(f"{name}_cache",
                     st["jit_fallbacks"] == 0 and st["cost_failures"] == 0
                     and st["serialize_failures"] == 0,
                     f"cache {st}")
    return mres, hres


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh round and its host "
                         "comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and labels")
    args = ap.parse_args(argv)

    try:
        from repro.launch import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's sources are not next to this "
              f"script ({ROOT / 'src'}): {e}", file=sys.stderr)
        return 2
    cache_dir = compile_cache.enable()
    import jax
    hits = {"/jax/compilation_cache/cache_hits": 0,
            "/jax/compilation_cache/cache_misses": 0}

    def count(event, **_):
        if event in hits:
            hits[event] += 1
    jax.monitoring.register_event_listener(count)

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's default device is {dev.platform!r} "
              f"({dev.device_kind}), not a TPU — no result", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: {len(devs)} TPU device(s), this phase needs "
              f"{want}", file=sys.stderr)
        return 1
    print(f"devices: {len(devs)} × {dev.device_kind} ({dev.platform}); "
          f"compile cache: {cache_dir}", flush=True)

    report = Report()
    t0 = time.perf_counter()
    if args.four_chips:
        mesh_round(report, seed=args.seed)
    else:
        service_round(report, seed=args.seed)
    report.timing("whole run", time.perf_counter() - t0)
    print(f"persistent compile cache: "
          f"{hits['/jax/compilation_cache/cache_hits']} hits, "
          f"{hits['/jax/compilation_cache/cache_misses']} misses in "
          f"{cache_dir}", flush=True)
    if report.failed:
        print(f"chip_smoke: failed checks {report.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
