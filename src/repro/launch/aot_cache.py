"""AOT executable cache for the FedPFT round program (DESIGN.md §11).

Serving many concurrent federations means every new cohort signature
(M, C, K, d, cov_type) used to pay full trace+compile inside the
request path.  :class:`ProgramCache` bounds that churn:

* cohorts are **canonicalized** — the slot-row count M rounds up to a
  power of two (``CohortSignature.canonical``, the planner's bucketing
  idiom) and the session pads with ``gmm.identity_gmm`` count-0 rows, so
  the cache cardinality is the small canonical grid, not the cohort-size
  lattice;
* each canonical signature is **AOT lowered+compiled** once
  (``round_program.lower(*round_specs_for(sig)).compile()``), costed via
  ``launch.hlo_cost``, and optionally round-tripped through
  ``jax.experimental.serialize_executable`` (the deployment artifact);
* entries live in an **LRU** of ``max_entries`` with hit/miss/evict/
  compile counters (``stats()``), surfaced in ``info["compile"]`` and the
  ``analysis_gate``/``compile_bench`` rows;
* a backend that cannot AOT-compile **falls back to the plain jit path**
  per entry instead of failing the round.  No failure vanishes: a failed
  compile, costing or serialization bumps its counter (``jit_fallbacks``,
  ``cost_failures``, ``serialize_failures``), keeps the exception on the
  entry (``CachedProgram.errors``) and logs a warning.
"""
from __future__ import annotations

import collections
import dataclasses
import logging
import time
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from repro.fl import round as FR
from repro.launch import input_specs as IS

__all__ = ["CachedProgram", "ProgramCache", "canonical_grid",
           "serving_grid", "mesh_fingerprint"]

log = logging.getLogger(__name__)


def mesh_fingerprint(mesh) -> Optional[Tuple]:
    """Hashable identity of a mesh for the cache key (None on the host
    path).  Same axes over the same devices ⇒ same executable."""
    if mesh is None:
        return None
    return (tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            tuple(int(d.id) for d in mesh.devices.ravel()))


@dataclasses.dataclass
class CachedProgram:
    """One cache entry: the compiled round program + its provenance.

    ``__call__`` runs the executable (or the jit fallback) with the round
    program's positional args.  ``serialized`` is the
    ``serialize_executable`` triple ``(payload, in_tree, out_tree)`` when
    the backend supports it — :meth:`deserialize` proves the round trip.
    ``errors`` maps each failed phase (``"compile"``, ``"cost"``,
    ``"serialize"``) to the exception it raised.
    """
    sig: FR.CohortSignature
    head_cfg: Any
    fingerprint: Optional[Tuple]
    executable: Any                     # jax.stages.Compiled, or None
    fallback: Any                       # jitted partial when AOT failed
    compile_us: float
    cost: Optional[Any]                 # hlo_cost.Cost of the executable
    serialized: Optional[Tuple[bytes, Any, Any]]
    uses: int = 0
    errors: Dict[str, Exception] = dataclasses.field(default_factory=dict)

    @property
    def aot(self) -> bool:
        return self.executable is not None

    def __call__(self, key, pi, mu, cov, counts, slot_labels):
        if self.executable is not None:
            return self.executable(key, pi, mu, cov, counts, slot_labels)
        return self.fallback(key, pi, mu, cov, counts, slot_labels)

    def deserialize(self):
        """Rebuild the executable from its serialized form (round-trip
        determinism is asserted in tests/test_aot_cache.py)."""
        if self.serialized is None:
            raise ValueError("CachedProgram: no serialized payload "
                             "(serialization unsupported or disabled)")
        from jax.experimental import serialize_executable as SE
        payload, in_tree, out_tree = self.serialized
        return SE.deserialize_and_load(payload, in_tree, out_tree)


class ProgramCache:
    """LRU of AOT-compiled round programs keyed on canonical signatures.

    One instance serves every ``FedSession`` path — host, mesh
    (``run_sharded``), and streaming ingest — so a multi-tenant server
    compiles each canonical (signature, head config, mesh) combination
    exactly once.  Thread-unsafe by design: the session loop is
    single-threaded; wrap externally if sharing across request threads.
    """

    def __init__(self, max_entries: int = 32, canonicalize: bool = True,
                 serialize: bool = True):
        if max_entries < 1:
            raise ValueError(f"ProgramCache: max_entries={max_entries}")
        self.max_entries = int(max_entries)
        self.canonicalize = bool(canonicalize)
        self.serialize = bool(serialize)
        self._entries: "collections.OrderedDict[Tuple, CachedProgram]" = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compiles = 0
        self.jit_fallbacks = 0
        self.cost_failures = 0
        self.serialize_failures = 0
        self.total_compile_us = 0.0

    # -- key space ----------------------------------------------------------

    def canonical(self, sig: FR.CohortSignature) -> FR.CohortSignature:
        return sig.canonical() if self.canonicalize else sig

    def _key(self, canon, head_cfg, mesh) -> Tuple:
        return (canon, head_cfg, mesh_fingerprint(mesh))

    # -- the cache ----------------------------------------------------------

    def get(self, sig: FR.CohortSignature, head_cfg,
            mesh=None) -> CachedProgram:
        """The compiled program for ``sig``'s canonical form — compiling,
        costing, and serializing it on first use."""
        canon = self.canonical(sig)
        ck = self._key(canon, head_cfg, mesh)
        entry = self._entries.get(ck)
        if entry is not None:
            self.hits += 1
            self._entries.move_to_end(ck)
            entry.uses += 1
            return entry
        self.misses += 1
        entry = self._compile(canon, head_cfg, mesh)
        entry.uses = 1
        self._entries[ck] = entry
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)
            self.evictions += 1
        return entry

    def _compile(self, canon, head_cfg, mesh) -> CachedProgram:
        statics = dict(sig=canon, head_cfg=head_cfg)
        t0 = time.perf_counter()
        executable = cost = serialized = None
        errors: Dict[str, Exception] = {}

        def failed(phase: str, e: Exception) -> None:
            errors[phase] = e
            log.warning("round program %s: %s failed", canon, phase,
                        exc_info=e)

        # each phase is a boundary that must keep serving: the counters,
        # the entry's ``errors`` and the log carry what went wrong
        try:
            specs = IS.round_specs_for(canon, mesh=mesh)
            lowered = FR.round_program.lower(*specs, **statics)
            executable = lowered.compile()
        except Exception as e:
            self.jit_fallbacks += 1
            failed("compile", e)
        compile_us = (time.perf_counter() - t0) * 1e6
        if executable is not None:
            self.compiles += 1
            self.total_compile_us += compile_us
            try:
                from repro.launch.hlo_cost import HloCost
                cost = HloCost(executable.as_text()).total()
            except Exception as e:
                self.cost_failures += 1
                failed("cost", e)
            if self.serialize:
                try:
                    from jax.experimental import serialize_executable as SE
                    serialized = SE.serialize(executable)
                except Exception as e:
                    self.serialize_failures += 1
                    failed("serialize", e)
        return CachedProgram(
            sig=canon, head_cfg=head_cfg,
            fingerprint=mesh_fingerprint(mesh), executable=executable,
            fallback=partial(FR.round_program, **statics),
            compile_us=compile_us, cost=cost, serialized=serialized,
            errors=errors)

    def warmup(self, sigs: Sequence[FR.CohortSignature], head_cfg,
               mesh=None) -> Dict[str, Any]:
        """Pre-compile a signature list (one pass over the canonical grid
        before serving) — returns :meth:`stats`."""
        for sig in sigs:
            self.get(sig, head_cfg, mesh=mesh)
        return self.stats()

    # -- introspection ------------------------------------------------------

    def __len__(self) -> int:
        return len(self._entries)

    def keys(self) -> List[Tuple]:
        """Cache keys in LRU order (oldest first) — eviction order."""
        return list(self._entries)

    def failures(self) -> Dict[Tuple, Dict[str, Exception]]:
        """The failed phases of each live entry that has any, by key."""
        return {k: e.errors for k, e in self._entries.items() if e.errors}

    def stats(self) -> Dict[str, Any]:
        return {"entries": len(self._entries), "hits": self.hits,
                "misses": self.misses, "evictions": self.evictions,
                "compiles": self.compiles,
                "jit_fallbacks": self.jit_fallbacks,
                "cost_failures": self.cost_failures,
                "serialize_failures": self.serialize_failures,
                "total_compile_us": self.total_compile_us}

    def snapshot(self) -> Dict[str, Any]:
        """``stats()`` frozen for a later :meth:`delta` — the
        zero-new-compiles assertion of the chaos/serve benchmarks:
        ``delta(before)["compiles"] == 0`` after warmup proves degraded
        partial-round closes reuse the warm program."""
        return self.stats()

    def delta(self, before: Dict[str, Any]) -> Dict[str, Any]:
        """Counter movement since ``before`` (a :meth:`snapshot`)."""
        now = self.stats()
        return {k: now[k] - before.get(k, 0) for k in now}


def canonical_grid(C: int, d: int, Ms: Sequence[int] = (4, 16, 64),
                   Ks: Sequence[int] = (1, 2, 4),
                   cov_types: Sequence[str] = ("diag",)
                   ) -> List[FR.CohortSignature]:
    """A small canonical signature grid to warm the cache with — every
    entry already canonical (Ms are slot-row counts and must be powers of
    two: this names the compile targets, it does not bucket)."""
    for m in Ms:
        if FR.next_pow2(m) != m:
            raise ValueError(f"canonical_grid: M={m} is not a power of two "
                             "— the grid names canonical shapes")
    return [FR.CohortSignature(M=m, C=C, K=k, d=d, cov_type=cov)
            for m in Ms for k in Ks for cov in cov_types]


def serving_grid(capacity: int, C: int, K: int, d: int,
                 cov_types: Sequence[str] = ("diag",)
                 ) -> List[FR.CohortSignature]:
    """The signatures a streaming-ingest service will actually request.

    The broker's reservoir always closes as a float32 slot stack of its
    fixed ``capacity`` rows, so the warm set is exactly one canonical
    signature per covariance type — warm these at boot
    (``FedPFTService.warmup``) and ``close_round`` never compiles in the
    request path.  Pass the same ``head_cfg`` the cached ingest round
    uses, i.e. ``cache.warmup(serving_grid(...), session.head)``.
    """
    M = FR.next_pow2(capacity)
    return [FR.CohortSignature(M=M, C=C, K=K, d=d, cov_type=cov)
            for cov in cov_types]
