"""CHURN-RETRACE: trace every registered public jitted entry point across
its canonical shape grid and flag compile-cache forks.

The registry below names the repo's jitted entry points together with a
ShapeDtypeStruct builder per canonical shape case.  Shape cases derive
from the `launch/input_specs.py` grid (train_4k / prefill_32k / decode
batch geometry) scaled onto the federation workload's axes (N samples, d
features, K components, B fits), so the grid the analyzer traces is the
grid the dry-run lowers.

Checks per (entry, case):

* the entry traces at all (an untraceable public entry is an ERROR);
* tracing twice with identical abstract inputs yields an identical
  jaxpr — a mismatch means a Python-scalar closure, global state, or a
  shape-dependent Python branch forks the compile cache nondeterministically;
* every declared static argument value is hashable (an unhashable static
  fails at the first real call).

This rule is the precondition for the ROADMAP's AOT round-program cache:
an entry that retraces nondeterministically can never be cached ahead of
time.  ``grid_report()`` exposes the per-entry jaxpr counts that
``benchmarks/analysis_gate.py`` emits as ``analysis/*`` rows.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.analysis.core import (Finding, SemanticRule, Severity,
                                 SourceFile)


@dataclasses.dataclass(frozen=True)
class Entry:
    """One public jitted entry point + its canonical shape grid."""
    name: str                      # "module.attr" for reporting
    anchor: str                    # repo-relative file the finding lands on
    build: Callable[[], Callable]  # import + return the jitted callable
    cases: Callable[[], Sequence[Tuple[str, tuple, dict]]]
    # cases() -> [(case_name, args, kwargs)] of ShapeDtypeStructs
    statics: Callable[[], Dict[str, object]] = lambda: {}


def _sds(shape, dtype=None):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, dtype or jnp.float32)


def _key_sds():
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct((2,), jnp.uint32)


def _feature_grid() -> List[Tuple[str, int, int]]:
    """(case, N, d) pairs scaled from the canonical input-shape grid:
    per-client sample counts track the global batch axis, feature dims the
    reduced model width (models/config.py reduced() default)."""
    from repro.models.config import INPUT_SHAPES
    train = INPUT_SHAPES["train_4k"]
    decode = INPUT_SHAPES["decode_32k"]
    return [("train_batch", train.global_batch, 64),
            ("decode_batch", decode.global_batch, 64)]


def _estep_cases():
    import jax.numpy as jnp
    out = []
    for case, N, d in _feature_grid():
        out.append((case,
                    (_sds((1, N, d)), _sds((4, 8, d)), _sds((4, 8, d)),
                     _sds((4, 8))),
                    {"interpret": True}))
    return out


def _flash_cases():
    import jax.numpy as jnp
    from repro.models.config import INPUT_SHAPES
    out = []
    for name in ("train_4k", "prefill_32k"):
        S = INPUT_SHAPES[name].seq_len
        q = _sds((1, 4, S, 64))
        kv = _sds((1, 2, S, 64))
        out.append((name, (q, kv, kv), {"interpret": True}))
    # decode: one query against a long cache
    S = INPUT_SHAPES["decode_32k"].seq_len
    out.append(("decode_32k",
                (_sds((1, 4, 1, 64)), _sds((1, 2, S, 64)),
                 _sds((1, 2, S, 64))), {"interpret": True}))
    return out


def _train_head_cases():
    import jax.numpy as jnp
    from repro.core.head import HeadConfig
    cfg = HeadConfig(n_steps=8)
    out = []
    for case, N, d in _feature_grid():
        out.append((case,
                    (_key_sds(), _sds((N, d)), _sds((N,), jnp.int32), 16,
                     cfg), {}))
    return out


def _fit_gmm_batch_cases():
    import jax.numpy as jnp
    from repro.core.gmm import GMMConfig
    from repro.kernels import ops
    cfg = GMMConfig(n_components=4, cov_type="diag", n_iter=3)
    out = []
    for case, N, d in _feature_grid():
        out.append((case,
                    (_sds((2, 2), jnp.uint32), _sds((2, N, d)),
                     _sds((2, N)), cfg, ops.backend()), {}))
    return out


def _local_train_cases():
    import jax.numpy as jnp
    out = []
    for case, N, d in _feature_grid():
        head = {"w": _sds((d, 16)), "b": _sds((16,))}
        out.append((case,
                    (_key_sds(), head, _sds((N, d)),
                     _sds((N,), jnp.int32), 16), {"n_steps": 4}))
    return out


def _sample_stacked_cases():
    import jax.numpy as jnp
    S, K, d = 64, 4, 32
    args = (_key_sds(), _sds((S,), jnp.int32), _sds((S, K)),
            _sds((S, K, d)), _sds((S, K, d)), S, "diag")
    return [("slot_64", args, {})]


def _round_sigs():
    """The round program's canonical mini-grid: a cov-type spread at
    power-of-two slot counts (what launch.aot_cache compiles for)."""
    from repro.fl.round import CohortSignature
    return [
        CohortSignature(M=4, C=8, K=2, d=32, cov_type="diag"),
        CohortSignature(M=4, C=8, K=2, d=32, cov_type="full"),
        CohortSignature(M=16, C=8, K=2, d=32, cov_type="spher"),
        CohortSignature(M=64, C=8, K=2, d=32, cov_type="diag"),
    ]


def _round_program_cases():
    from repro.core.head import HeadConfig
    from repro.launch.input_specs import round_specs_for
    cfg = HeadConfig(n_steps=8)
    return [
        (f"{s.cov_type}/M{s.M}", tuple(round_specs_for(s)),
         {"sig": s, "head_cfg": cfg})
        for s in _round_sigs()
    ]


def cache_entry_points() -> List[Entry]:
    """Entry points served from the AOT executable cache
    (``launch.aot_cache``) — the CACHE-KEY rule's registry.  A per-case
    statics factory (``cases()`` kwargs) rebuilds the static values fresh
    on every call, which is exactly what hash-stability must survive."""
    return [
        Entry("fl.round.round_program", "repro/fl/round.py",
              lambda: _imp("repro.fl.round", "round_program"),
              _round_program_cases,
              lambda: {"sig": _round_sigs()[0],
                       "head_cfg": _imp("repro.core.head", "HeadConfig")(
                           n_steps=8)}),
    ]


def entry_points() -> List[Entry]:
    return [
        Entry("kernels.gmm_estep.estep_fused",
              "repro/kernels/gmm_estep.py",
              lambda: _imp("repro.kernels.gmm_estep", "estep_fused"),
              _estep_cases,
              lambda: {"block_n": 256, "block_k": 128, "interpret": True}),
        Entry("kernels.flash_attention.flash_attention",
              "repro/kernels/flash_attention.py",
              lambda: _imp("repro.kernels.flash_attention",
                           "flash_attention"),
              _flash_cases,
              lambda: {"causal": True, "window": 0, "prefix": 0,
                       "block_q": 128, "block_k": 128, "interpret": True}),
        Entry("core.head.train_head", "repro/core/head.py",
              lambda: _imp("repro.core.head", "train_head"),
              _train_head_cases,
              lambda: {"n_classes": 16,
                       "cfg": _imp("repro.core.head", "HeadConfig")(
                           n_steps=8)}),
        Entry("core.gmm._fit_gmm_batch", "repro/core/gmm.py",
              lambda: _imp("repro.core.gmm", "_fit_gmm_batch"),
              _fit_gmm_batch_cases,
              lambda: {"cfg": _imp("repro.core.gmm", "GMMConfig")(
                           n_components=4),
                       "backend": _imp("repro.kernels.ops", "backend")()}),
        Entry("fl.baselines.local_train", "repro/fl/baselines.py",
              lambda: _imp("repro.fl.baselines", "local_train"),
              _local_train_cases,
              lambda: {"n_classes": 16, "n_steps": 4, "batch_size": 256,
                       "lr": 1e-3, "prox": 0.0}),
        Entry("fl.api._sample_stacked", "repro/fl/api.py",
              lambda: _imp("repro.fl.api", "_sample_stacked"),
              _sample_stacked_cases,
              lambda: {"S": 64, "cov_type": "diag"}),
        # the AOT-cached round program rides the same double-trace grid —
        # CHURN-RETRACE guards its jaxpr determinism, CACHE-KEY its keys
        *cache_entry_points(),
    ]


def _imp(module: str, attr: str):
    import importlib
    return getattr(importlib.import_module(module), attr)


def trace_entry(entry: Entry) -> Tuple[List[str], List[Tuple[str, str]]]:
    """Trace one entry across its grid.

    Returns (jaxpr strings, one per case, each verified stable over a
    double trace) and a list of (case, error) failures.
    """
    fn = entry.build()
    jaxprs, errors = [], []
    for case, args, kwargs in entry.cases():
        try:
            first = str(fn.trace(*args, **kwargs).jaxpr)
            second = str(fn.trace(*args, **kwargs).jaxpr)
        except Exception as e:  # noqa: BLE001 — any trace failure is a finding
            errors.append((case, f"{type(e).__name__}: {e}"))
            continue
        if first != second:
            errors.append((case, "RETRACE-DIVERGED"))
        jaxprs.append(first)
    return jaxprs, errors


def grid_report() -> Dict[str, Dict[str, float]]:
    """Per-entry trace stats for the benchmark gate (analysis/* rows)."""
    import time
    report = {}
    for entry in entry_points():
        t0 = time.time()
        jaxprs, errors = trace_entry(entry)
        report[entry.name] = {
            "cases": len(jaxprs) + len(errors),
            "distinct_jaxprs": len(set(jaxprs)),
            "errors": len(errors),
            "us": (time.time() - t0) * 1e6,
        }
    return report


class RetraceRule(SemanticRule):
    id = "CHURN-RETRACE"
    severity = Severity.ERROR
    doc = ("a public jitted entry point fails to trace, retraces "
           "nondeterministically on identical abstract inputs, or carries "
           "an unhashable static argument")
    anchors = tuple(sorted({e.anchor for e in entry_points()}))

    def __init__(self, entries: Optional[Sequence[Entry]] = None):
        self.entries = entries

    def run_project(self, files: Sequence[SourceFile]):
        findings: List[Finding] = []
        by_anchor = {}
        for f in files:
            by_anchor[f.path.replace("\\", "/")] = f
        for entry in (self.entries if self.entries is not None
                      else entry_points()):
            src = next((f for p, f in by_anchor.items()
                        if p.endswith(entry.anchor)), None)
            if src is None:
                continue
            # static-arg hashability is checked by construction
            try:
                for name, val in entry.statics().items():
                    hash(val)
            except TypeError as e:
                findings.append(self.finding(
                    src, 1,
                    f"{entry.name}: static argument '{name}' is "
                    f"unhashable ({e})",
                    "make the static a frozen dataclass / tuple"))
                continue
            _, errors = trace_entry(entry)
            for case, err in errors:
                if err == "RETRACE-DIVERGED":
                    findings.append(self.finding(
                        src, 1,
                        f"{entry.name}[{case}]: two traces with identical "
                        f"abstract inputs produced different jaxprs — a "
                        f"Python-scalar closure or shape-dependent branch "
                        f"forks the compile cache",
                        "close only over hashable statics; branch on "
                        "abstract shapes, not values"))
                else:
                    findings.append(self.finding(
                        src, 1,
                        f"{entry.name}[{case}] failed to trace on its "
                        f"canonical grid: {err}",
                        "public jitted entries must trace for every "
                        "canonical shape (launch/input_specs.py)"))
        return findings


class CacheKeyRule(SemanticRule):
    """CACHE-KEY: invariants the AOT executable cache keys on.

    ``launch.aot_cache.ProgramCache`` keys entries on ``(CohortSignature,
    HeadConfig, mesh fingerprint)`` and assumes a key
    that compares equal ALWAYS maps to one executable.  Two ways that
    breaks: a static whose hash isn't stable across reconstruction (a
    dataclass growing an unhashable or identity-hashed field — every
    request would miss), and a round program whose jaxpr differs between
    traces of the same abstract inputs (one key, many executables).  Both
    are checked here on the live modules, per entry in
    :func:`cache_entry_points`.
    """

    id = "CACHE-KEY"
    severity = Severity.ERROR
    doc = ("an AOT-cached entry point's statics don't hash/compare stably "
           "across reconstruction, or its jaxpr forks across traces of "
           "one cache key")
    anchors = ("repro/fl/round.py", "repro/launch/aot_cache.py")

    def __init__(self, entries: Optional[Sequence[Entry]] = None):
        self.entries = entries

    def run_project(self, files: Sequence[SourceFile]):
        findings: List[Finding] = []
        src = next((f for f in files
                    if f.path.replace("\\", "/").endswith(self.anchors[0])),
                   files[0])
        for entry in (self.entries if self.entries is not None
                      else cache_entry_points()):
            # statics rebuilt twice from the factory must be equal AND
            # hash-equal — the cache-key stability a dict lookup needs
            try:
                first, second = entry.statics(), entry.statics()
            except Exception as e:  # noqa: BLE001 — broken factory gates
                findings.append(self.finding(
                    src, 1, f"{entry.name}: statics factory failed ({e})",
                    "cache_entry_points() statics must construct cleanly"))
                continue
            for name in first:
                try:
                    stable = (first[name] == second[name]
                              and hash(first[name]) == hash(second[name]))
                except TypeError as e:
                    findings.append(self.finding(
                        src, 1,
                        f"{entry.name}: static '{name}' is unhashable "
                        f"({e}) — it can never key the executable cache",
                        "make the static a frozen dataclass / tuple"))
                    continue
                if not stable:
                    findings.append(self.finding(
                        src, 1,
                        f"{entry.name}: static '{name}' rebuilt from the "
                        f"same factory compares or hashes unequal — every "
                        f"request would miss the cache",
                        "derive __eq__/__hash__ from value fields only "
                        "(frozen dataclass)"))
            # one cache key ⇒ one jaxpr: reuse the double-trace machinery
            _, errors = trace_entry(entry)
            for case, err in errors:
                msg = (f"{entry.name}[{case}]: jaxpr diverged across two "
                       f"traces of one cache key — the cached executable "
                       f"would not match a fresh compile"
                       if err == "RETRACE-DIVERGED" else
                       f"{entry.name}[{case}] failed to trace: {err}")
                findings.append(self.finding(
                    src, 1, msg,
                    "keep round_program's shapes a pure function of "
                    "CohortSignature"))
        return findings
