"""FedPFT-as-a-service: one process closing the paper's loop (DESIGN.md §12).

The paper's pipeline — foundation-model feature extraction → per-client
GMM fitting → one-shot transfer → global head (§3, Alg. 1) — runs here as
a *service*: the backbone is served with continuous batching for
**extraction** traffic (prefill-heavy: a whole prompt per request),
clients fit GMMs against those features and submit wire messages through
the session's :class:`~repro.fl.ingest.IngestBroker`, and once a round
closes the trained global head serves **inference** traffic (decode-light:
one masked forward + a head matmul).

Both traffic classes draw from ONE fixed pool of ``n_slots`` batch rows —
the continuous-batching slot discipline of :class:`serve.server
.BatchedServer` applied to feature extraction.  Admission is
traffic-class aware: when both queues are non-empty, extraction is
guaranteed ``ceil(extract_share · n_slots)`` rows and inference the rest;
an under-full class backfills the other's rows, so neither class can
starve the pool.  A step's batch is ``S_bucket`` wide, the power-of-two
bucket of its longest prompt.  Where the feature step packs (the
``dense`` and ``hybrid`` families, ``serve.make_feature_step``), the
admitted prompts share rows: ``R`` rows, a power of two ≤ ``n_slots``;
otherwise each takes a row of ``n_slots``.  So the compile count is bounded by the prompt
buckets times the row counts, never by traffic.

The round program sits behind the session's
:class:`~repro.launch.aot_cache.ProgramCache`: :meth:`warmup` pre-compiles
the one slot signature the broker can close with
(``aot_cache.serving_grid``), so extract, train, and infer share one warm
cache and :meth:`close_round` never compiles in the request path.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import head as H
from repro.fl import ingest as IG
from repro.launch import aot_cache as AC
from repro.models.config import ModelConfig

EXTRACT = "extract"
INFER = "infer"

# extract admission policies near the round deadline (DESIGN.md §13):
# "shed" refuses with AdmissionError, "defer" parks the request for the
# next round
SHED = "shed"
DEFER = "defer"


class AdmissionError(RuntimeError):
    """An extract request was refused: too close to the round deadline.

    Raised only under ``extract_admission="shed"`` — a feature extracted
    with less than ``deadline_guard_s`` of round left cannot be fitted,
    encoded, and submitted before the broker seals, so the work would be
    wasted device time.  The client should retry next round (or the
    deployment should use ``"defer"`` to have the service hold it).
    """


@dataclasses.dataclass
class ServiceRequest:
    """One request: a token prompt plus its latency lifecycle.

    ``t_submit``/``t_admit``/``t_done`` are clock readings at enqueue,
    slot admission, and completion — queueing delay and service time are
    separable in :meth:`FedPFTService.stats`.
    """
    rid: int
    kind: str                      # EXTRACT | INFER
    tokens: np.ndarray             # (L,) prompt
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_done: float = 0.0
    feats: Optional[np.ndarray] = None   # (d,) — extraction result
    label: Optional[int] = None          # head argmax — inference result
    done: bool = False
    deferred: bool = False         # parked past a deadline, re-enqueued
                                   # at the next close_round


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    n_slots: int = 8
    max_seq: int = 64
    min_bucket: int = 8
    extract_share: float = 0.5     # guaranteed extract fraction of the pool
    # admission control near the broker deadline: an extract arriving with
    # < deadline_guard_s of round left can't round-trip (extract → fit →
    # submit) before the broker seals.  0.0 disables the guard; the guard
    # is inert anyway when the session's IngestConfig has no deadline.
    deadline_guard_s: float = 0.0
    extract_admission: str = SHED  # SHED refuses, DEFER parks to next round

    def __post_init__(self):
        if not 0.0 <= self.extract_share <= 1.0:
            raise ValueError(f"ServiceConfig: extract_share="
                             f"{self.extract_share} must be in [0, 1]")
        if self.n_slots < 1:
            raise ValueError(f"ServiceConfig: n_slots={self.n_slots}")
        if self.deadline_guard_s < 0.0:
            raise ValueError(f"ServiceConfig: deadline_guard_s="
                             f"{self.deadline_guard_s} must be >= 0")
        if self.extract_admission not in (SHED, DEFER):
            raise ValueError(f"ServiceConfig: extract_admission="
                             f"{self.extract_admission!r} not in "
                             f"({SHED!r}, {DEFER!r})")


class FedPFTService:
    """The serving loop: extract / ingest / train / infer in one process.

    ``session`` must be a ``FedSession(ingest=IngestConfig(...))`` — the
    session owns the admission policy, reservoir capacity, and (via
    ``program_cache=``) the AOT round cache; the service adds the
    request-level slot pool in front and the served head behind.
    """

    def __init__(self, cfg: ModelConfig, params, session,
                 scfg: ServiceConfig = ServiceConfig(),
                 clock=time.perf_counter):
        if session.ingest is None:
            raise ValueError(
                "FedPFTService needs FedSession(ingest=IngestConfig(...)): "
                "client GMM messages stream through the session's broker — "
                "an unbounded message list defeats the service memory law")
        from repro import serve as _serve
        self.cfg, self.params, self.session, self.scfg = \
            cfg, params, session, scfg
        self.clock = clock
        self._serve = _serve
        step = _serve.make_feature_step(cfg)
        self._layout = (_serve.pack_batch if getattr(step, "packs", False)
                        else _serve.pad_batch)
        self._feats = jax.jit(step)
        self._head_logits = jax.jit(H.head_logits)
        self.head: Optional[Dict] = None          # installed by close_round
        self.broker = self._fresh_broker()
        self.queues: Dict[str, Deque[ServiceRequest]] = {
            EXTRACT: collections.deque(), INFER: collections.deque()}
        self.rounds = 0
        self.steps = 0
        self.rows_computed = 0         # Σ rows over steps
        self.positions_computed = 0    # Σ rows × bucket over steps
        self.real_tokens = 0           # Σ prompt tokens admitted
        self._next_rid = 0
        self.completed: Dict[str, List[ServiceRequest]] = {
            EXTRACT: [], INFER: []}
        self.rejected_no_head = 0
        self.shed_extracts = 0
        self.deferred_extracts = 0
        self._deferred: Deque[ServiceRequest] = collections.deque()

    def _fresh_broker(self) -> IG.IngestBroker:
        return IG.IngestBroker(self.session.ingest, self.session.n_classes,
                               samples_per_class=self.session
                               .samples_per_class, clock=self.clock)

    # -- request ingress ----------------------------------------------------

    def _enqueue(self, kind: str, tokens) -> ServiceRequest:
        tokens = np.asarray(tokens)
        if tokens.ndim != 1 or tokens.shape[0] < 1:
            raise ValueError(f"FedPFTService: prompt must be (L≥1,), got "
                             f"shape {tokens.shape}")
        if tokens.shape[0] > self.scfg.max_seq:
            raise ValueError(f"FedPFTService: prompt length "
                             f"{tokens.shape[0]} > max_seq "
                             f"{self.scfg.max_seq}")
        req = ServiceRequest(rid=self._next_rid, kind=kind, tokens=tokens,
                             t_submit=self.clock())
        self._next_rid += 1
        self.queues[kind].append(req)
        return req

    def submit_extract(self, tokens) -> ServiceRequest:
        """Queue a feature-extraction request (a client's raw sample).

        Near the round deadline (less than ``deadline_guard_s`` of broker
        time left) the request is shed (:class:`AdmissionError`) or
        deferred to the next round, per ``extract_admission`` — features
        that cannot make it back through fit + submit before the broker
        seals are wasted device time.
        """
        guard = self.scfg.deadline_guard_s
        if guard > 0.0:
            left = self.broker.time_remaining()
            if left is not None and left < guard:
                if self.scfg.extract_admission == SHED:
                    self.shed_extracts += 1
                    raise AdmissionError(
                        f"FedPFTService: {left:.3f}s left in the round < "
                        f"deadline_guard_s={guard}s — extraction cannot "
                        f"complete the fit/submit round-trip; retry next "
                        f"round")
                req = ServiceRequest(rid=self._next_rid, kind=EXTRACT,
                                     tokens=np.asarray(tokens),
                                     t_submit=self.clock(), deferred=True)
                self._next_rid += 1
                self.deferred_extracts += 1
                self._deferred.append(req)
                return req
        return self._enqueue(EXTRACT, tokens)

    def submit_infer(self, tokens) -> ServiceRequest:
        """Queue a classification request against the served global head."""
        if self.head is None:
            self.rejected_no_head += 1
            raise RuntimeError(
                "FedPFTService: no head is being served yet — inference "
                "opens after the first close_round()")
        return self._enqueue(INFER, tokens)

    def submit_update(self, client_id: int, message) -> str:
        """Forward a client's GMM wire message to the round's broker.

        Returns the broker verdict (``admitted``/``late``/``duplicate``/
        ``over_capacity``/``quarantined``/``closed``) so the client can
        react — quarantined payloads are rejected at the wire without
        touching the reservoir (DESIGN.md §13).
        """
        return self.broker.submit(client_id, message)

    # -- the serving step ---------------------------------------------------

    def _admit(self) -> List[ServiceRequest]:
        """Pull ≤ n_slots requests across both classes.

        Extraction is guaranteed ``ceil(extract_share · n_slots)`` rows
        when both queues wait; whatever one class leaves unused, the
        other backfills — the pool is never idle while work is queued.
        """
        B = self.scfg.n_slots
        ext, inf = self.queues[EXTRACT], self.queues[INFER]
        if ext and inf:
            n_ext = min(len(ext),
                        int(np.ceil(self.scfg.extract_share * B)))
        else:
            n_ext = min(len(ext), B)
        batch = [ext.popleft() for _ in range(n_ext)]
        batch += [inf.popleft() for _ in range(min(len(inf),
                                                   B - len(batch)))]
        while len(batch) < B and ext:          # backfill unused infer rows
            batch.append(ext.popleft())
        return batch

    def step(self) -> int:
        """One serving step: admit, batch, extract, classify.

        Returns the number of requests completed.  The batch is
        ``S_bucket`` wide, the power-of-two bucket of the longest admitted
        prompt.  For a packing feature step (the ``dense`` and ``hybrid``
        families) the prompts are packed first-fit-decreasing into ``R``
        rows, ``R`` the power of two at or above the rows used
        (≤ ``n_slots``), and the call takes ``(R, S_bucket)`` tokens and
        ``(R, n_slots)`` segment lengths (``serve.pack_batch``): each
        prompt attends, mixes and pools only within its own segment.
        ``ssm`` (recurrent state would cross segments) and ``moe``
        (capacity routing makes features depend on batch-mates) keep one
        prompt per row: an ``(n_slots,
        S_bucket)`` call, short rows right-padded, unused rows of length 0
        (``serve.pad_batch``).  Either way request ``i`` gets feature row
        ``i``.
        """
        with obs.span("service.step", step=self.steps):
            with obs.span("service.admit"):
                batch = self._admit()
                if not batch:
                    return 0
                t_admit = self.clock()
                bucket = self._serve.pow2_bucket(
                    max(r.tokens.shape[0] for r in batch),
                    self.scfg.min_bucket, self.scfg.max_seq)
                tokens, length = self._layout([r.tokens for r in batch],
                                              bucket, self.scfg.n_slots)
                for r in batch:
                    r.t_admit = t_admit
            with obs.span("service.dispatch"):
                feats = self._feats(self.params, jnp.asarray(tokens),
                                    jnp.asarray(length))
                infer_rows = [i for i, r in enumerate(batch)
                              if r.kind == INFER]
                if infer_rows:
                    labels = jnp.argmax(
                        self._head_logits(self.head, feats), axis=-1)
            with obs.span("service.readback"):
                feats_h = np.asarray(jax.device_get(feats))
                labels_h = (np.asarray(jax.device_get(labels))
                            if infer_rows else None)
            t_done = self.clock()
            for i, r in enumerate(batch):
                if r.kind == EXTRACT:
                    r.feats = feats_h[i]
                else:
                    r.label = int(labels_h[i])
                r.t_done, r.done = t_done, True
                self.completed[r.kind].append(r)
            self.steps += 1
            self.rows_computed += tokens.shape[0]
            self.positions_computed += tokens.size
            self.real_tokens += int(length.sum())
            return len(batch)

    def drain(self) -> int:
        """Step until both queues are empty; returns requests completed."""
        n = 0
        while self.queues[EXTRACT] or self.queues[INFER]:
            n += self.step()
        return n

    # -- the FL round -------------------------------------------------------

    def close_round(self, key):
        """Close the broker, train the global head, start serving it.

        Key plumbing is :meth:`FedSession.aggregate_from_broker`'s — the
        service head is bit-identical to the offline session's on the
        same admitted cohort.  A fresh broker opens for the next round,
        and extracts deferred past the old round's deadline re-enter the
        work queue against it.
        """
        with obs.span("service.close_round", round=self.rounds):
            result = self.session.aggregate_from_broker(key, self.broker)
            with obs.span("service.install"):
                self.head = result.model
                self.broker = self._fresh_broker()
                self.rounds += 1
                while self._deferred:
                    self.queues[EXTRACT].append(self._deferred.popleft())
        return result

    def warmup(self, d: int) -> Dict:
        """Pre-compile the round program for this service's one closing
        signature (``aot_cache.serving_grid``) — no-op without a
        ``program_cache`` on the session."""
        cache = self.session.program_cache
        if cache is None:
            return {}
        summ = self.session.summarizer
        sigs = AC.serving_grid(self.session.ingest.capacity,
                               self.session.n_classes,
                               summ.gmm.n_components, d,
                               cov_types=(summ.cov_type,))
        return cache.warmup(sigs, self.session.head)

    # -- introspection ------------------------------------------------------

    def feature_compiles(self) -> int:
        """Compiled feature-step variants (≤ #prompt buckets × #row
        counts)."""
        return self._feats._cache_size()

    def stats(self) -> Dict:
        """Throughput + latency per traffic class, broker accounting, and
        the feature step's work: rows and positions computed (rows ×
        bucket per step) against the real prompt tokens among them.
        Packing's engagement is ``1 − rows_computed ÷ (steps · n_slots)``.
        """
        out: Dict = {"steps": self.steps, "rounds": self.rounds,
                     "rows_computed": self.rows_computed,
                     "positions_computed": self.positions_computed,
                     "real_tokens": self.real_tokens,
                     "rejected_no_head": self.rejected_no_head,
                     "shed_extracts": self.shed_extracts,
                     "deferred_extracts": self.deferred_extracts,
                     "deferred_pending": len(self._deferred),
                     "feature_compiles": self.feature_compiles(),
                     "ingest": self.broker.accounting()}
        for kind, reqs in self.completed.items():
            if not reqs:
                out[kind] = {"n": 0}
                continue
            lat = np.asarray([r.t_done - r.t_submit for r in reqs])
            span = (max(r.t_done for r in reqs)
                    - min(r.t_submit for r in reqs))
            out[kind] = {
                "n": len(reqs),
                "rps": len(reqs) / span if span > 0 else float("inf"),
                "p50_us": float(np.percentile(lat, 50) * 1e6),
                "p99_us": float(np.percentile(lat, 99) * 1e6),
            }
        return out
