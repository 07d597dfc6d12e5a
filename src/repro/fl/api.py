"""Unified federation API — one message schema, one codec, one server phase.

Layering (DESIGN.md §2): every FedPFT variant in this repo is the same four
orthogonal pieces composed by a :class:`FedSession`:

    Summarizer   what a client distills its data into (per-class GMMs today;
                 locally-trained heads for the one-shot baselines; the slot
                 is open for other parametric summaries)
    WireCodec    how a summary becomes bytes — a REAL quantize → serialize →
                 dequantize round-trip, so ``comm_bytes == len(payload)`` and
                 downstream accuracy is measured on the *decoded* parameters
    Topology     who talks to whom: ``Star`` (clients → server), ``Chain``
                 (client i → i+1, §4.2), ``Ring`` (chain with wraparound laps)
    privacy      an optional DP hook applied to the summary *before* encoding
                 (Theorem 4.1's Gaussian mechanism)

Server-side synthesis never needs the pool (DESIGN.md §2): every GMM
route — the message list (host and mesh), the ingest session and
:meth:`FedSession.aggregate_from_broker` — builds ONE flat mixture-slot
stack and hands it to ONE runner (:meth:`FedSession._fused_round`), which
trains the head straight from it: every Adam step draws its minibatch
inside one jitted scan (``core.head.train_head_from_gmms``, or the AOT
round program of ``fl.round`` when a ``program_cache`` is set), so the
pooled ``(N, d)`` tensor never exists.  Only a heterogeneous cohort (mixed
K or covariance family, paper §6.3), which cannot share one slot stack,
falls back to materializing the pool: the count-stratified planner
(:mod:`repro.fl.planner`) draws it one jitted sample per power-of-two
count bucket (≤ 2·Σcounts draws under any skew), keyed per *global*
(client, class) slot, and ``core.head.train_head`` trains on it.

Mesh execution (DESIGN.md §5): ``FedSession(mesh=…)`` or ``shards=n``
routes the round through :meth:`FedSession.run_sharded` — client fits as
one ``shard_map``'d batched EM per shard, the bf16 wire crossing the mesh
in a single ``all_gather`` (``core.distributed.fedpft_transfer``), and
the server phase replicated on every shard.  The wire
layout is ONE contract shared with the host codec (``gmm.WIRE_FIELDS`` /
``gmm.tril_pack``): :func:`messages_from_wire` turns the gathered pytree
into byte-accurate :class:`ClientMessage`s.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from repro import obs
from repro.core import distributed as DF
from repro.core import dp as DP
from repro.core import gmm as G
from repro.core import head as H
from repro.fl import ingest as IG
from repro.fl import planner as P
from repro.fl import resilience as RS
from repro.fl import round as FR

__all__ = [
    "QuantizedCodec", "WireHeader", "ClientMessage", "GMMSummarizer",
    "HeadSummarizer", "Star", "Chain", "Ring", "FedSession", "SessionResult",
    "encode_message", "stack_messages", "messages_from_wire",
    "decode_payload", "fused_slot_stack", "synthesize_batched",
    "synthesize_chunks", "synthesize_groups", "synthesize_looped",
]

# ---------------------------------------------------------------------------
# wire codec
# ---------------------------------------------------------------------------

_WIRE_DTYPES = {
    "float16": np.float16,
    "bfloat16": ml_dtypes.bfloat16,
    "float32": np.float32,
}

# serialization order of the GMM wire pytree — THE layout contract lives in
# core/gmm (shared with the in-mesh pack_wire path), not here
_GMM_FIELDS = G.WIRE_FIELDS
_HEAD_FIELDS = ("w", "b")


@dataclasses.dataclass(frozen=True)
class QuantizedCodec:
    """fp16 / bf16 / fp32 wire codec over flat parameter pytrees.

    ``encode`` quantizes each leaf to ``dtype`` and concatenates raw bytes
    in a fixed field order; ``decode`` reverses it and *dequantizes back to
    f32* — so whatever the server computes on has actually been through the
    wire precision.  ``len(encode(t))`` is exactly
    ``n_scalars(t) * bytes_per_scalar`` — Eqs. 9-11 with no hidden framing
    (schema metadata travels in the out-of-band :class:`WireHeader`).
    """
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert self.dtype in _WIRE_DTYPES, self.dtype

    @property
    def bytes_per_scalar(self) -> int:
        return np.dtype(_WIRE_DTYPES[self.dtype]).itemsize

    def encode(self, arrays: Dict[str, Any], fields: Sequence[str]) -> bytes:
        wd = _WIRE_DTYPES[self.dtype]
        return b"".join(
            np.ascontiguousarray(
                np.asarray(jax.device_get(arrays[f])).astype(wd)).tobytes()
            for f in fields)

    def decode(self, payload: bytes, shapes: Dict[str, Tuple[int, ...]],
               fields: Sequence[str]) -> Dict[str, np.ndarray]:
        wd = _WIRE_DTYPES[self.dtype]
        itemsize = np.dtype(wd).itemsize
        out, off = {}, 0
        for f in fields:
            n = int(np.prod(shapes[f], dtype=np.int64)) if shapes[f] else 1
            raw = np.frombuffer(payload, dtype=wd, count=n, offset=off)
            out[f] = raw.astype(np.float32).reshape(shapes[f])
            off += n * itemsize
        assert off == len(payload), (off, len(payload))
        return out

    def decode_checked(self, payload: bytes,
                       shapes: Dict[str, Tuple[int, ...]],
                       fields: Sequence[str]
                       ) -> Tuple[Optional[Dict[str, np.ndarray]],
                                  Optional[str]]:
        """Validating :meth:`decode`: ``(params, None)`` on a clean
        payload, ``(None, reason)`` on a length mismatch, ``(params,
        reason)`` on non-finite scalars — never raises, so the broker can
        quarantine a corrupted message instead of crashing the round
        (DESIGN.md §13).
        """
        wd = _WIRE_DTYPES[self.dtype]
        itemsize = np.dtype(wd).itemsize
        want = sum(int(np.prod(shapes[f], dtype=np.int64)) if shapes[f]
                   else 1 for f in fields) * itemsize
        if len(payload) != want:
            return None, (f"length_mismatch: payload is {len(payload)} "
                          f"bytes, schema says {want}")
        out = self.decode(payload, shapes, fields)
        bad = G.nonfinite_fields(out, tuple(fields))
        if bad:
            return out, (f"non_finite: fields {bad} carry NaN/Inf "
                         "after decode")
        return out, None


@dataclasses.dataclass(frozen=True)
class WireHeader:
    """Out-of-band message metadata (schema, shapes, provenance).

    Deliberately *not* counted against ``comm_bytes``: it is O(C) ints of
    negotiated schema, vs O(C·K·d²) payload scalars — the paper's cost model
    (Eqs. 9-11) counts parameters only, and so do we.
    """
    kind: str                      # "gmm" | "head"
    cov_type: str                  # GMM family ("" for head messages)
    d: int                         # feature dim
    K: int                         # mixture components (1 for head)
    n_classes: int
    counts: Tuple[int, ...]        # per-class sample counts, len C
    dtype: str                     # codec dtype the payload was written in

    @property
    def present(self) -> Tuple[int, ...]:
        return tuple(int(c) for c in range(self.n_classes)
                     if self.counts[c] > 0)


def _packed_cov_shape(cov_type: str, Cp: int, K: int, d: int):
    """Wire shape of ``Cp`` present classes' cov leaf — gmm owns the layout."""
    return (Cp,) + G.packed_cov_shape(cov_type, K, d)


def _pack_cov(cov: np.ndarray, cov_type: str) -> np.ndarray:
    """(…, d, d) full covariances → lower-triangle scalars; others pass.

    Delegates to ``gmm.tril_pack`` — the ONE row-major tril wire layout
    shared with ``gmm.pack_wire``/``unpack_wire``; ``comm_bytes``
    (Eqs. 9-11) counts exactly these scalars.
    """
    if cov_type != "full":
        return cov
    return np.asarray(G.tril_pack(cov))


def _unpack_cov(packed: np.ndarray, cov_type: str, d: int) -> np.ndarray:
    if cov_type != "full":
        return packed
    return G.tril_unpack(np.asarray(packed, np.float32), d)


# ---------------------------------------------------------------------------
# ClientMessage v2 — a pytree whose leaves are the DECODED parameters
# ---------------------------------------------------------------------------


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ClientMessage:
    """v2 wire message: encoded payload + its decoded stacked parameters.

    ``params`` holds the post-round-trip (quantized→dequantized) f32 arrays
    stacked over the class axis — ``pi (C,K)``, ``mu (C,K,d)``, ``cov
    (C,K,…)`` for GMM messages, ``w (d,C)`` / ``b (C,)`` for head messages —
    so a list of homogeneous messages stacks into the server's ``(M, C, K,
    …)`` batch with one ``tree.map``.  The raw ``payload`` is what crossed
    the wire; ``comm_bytes == len(payload)`` by construction.
    """
    params: Dict[str, jax.Array]
    logliks: Tuple[float, ...]     # hashable, so treedefs stay jit-safe
    header: WireHeader
    payload: bytes

    # -- pytree protocol (params are the traced leaves) --
    def tree_flatten(self):
        return (self.params,), (self.logliks, self.header, self.payload)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(params=children[0], logliks=aux[0], header=aux[1],
                   payload=aux[2])

    @property
    def counts(self) -> np.ndarray:
        return np.asarray(self.header.counts, np.int64)

    @property
    def comm_bytes(self) -> int:
        return len(self.payload)

    def wire_bytes(self) -> int:
        """Actual encoded payload length (``== comm_bytes``).

        Unlike the v1 estimator this takes no arguments: the v2 message
        carries its real payload, so there is nothing to parameterize —
        callers migrating from v1 drop the ``(cov_type, bytes_per_scalar)``
        arguments rather than have them silently swallowed.
        """
        return len(self.payload)


def encode_message(params: Dict, counts, logliks, *, kind: str,
                   cov_type: str, n_classes: int,
                   codec: QuantizedCodec) -> ClientMessage:
    """Client → wire: subset to present classes, quantize, serialize.

    Returns the message carrying both the payload bytes and the decoded
    (round-tripped) parameters the receiver will actually compute on.
    """
    with obs.span("client.readback"):
        counts = np.asarray(jax.device_get(counts)).astype(np.int64).ravel()
        params = {k: np.asarray(jax.device_get(v), np.float32)
                  for k, v in params.items()}
        lls = np.asarray(jax.device_get(logliks), np.float32).ravel()
    with obs.span("wire.encode"):
        return _encode(params, counts, lls, kind=kind, cov_type=cov_type,
                       n_classes=n_classes, codec=codec)


def _encode(params: Dict[str, np.ndarray], counts: np.ndarray,
            lls: np.ndarray, *, kind: str, cov_type: str, n_classes: int,
            codec: QuantizedCodec) -> ClientMessage:
    """:func:`encode_message` on host arrays: subset, quantize, serialize,
    decode, and upload the decoded parameters."""
    if kind == "gmm":
        K, d = params["mu"].shape[-2], params["mu"].shape[-1]
        present = np.flatnonzero(counts > 0)
        sub = {"pi": params["pi"][present],
               "mu": params["mu"][present],
               "cov": _pack_cov(params["cov"][present], cov_type)}
        fields = _GMM_FIELDS
        shapes = {"pi": (len(present), K), "mu": (len(present), K, d),
                  "cov": _packed_cov_shape(cov_type, len(present), K, d)}
    elif kind == "head":
        d = params["w"].shape[0]
        K = 1
        sub = {"w": params["w"], "b": params["b"]}
        fields = _HEAD_FIELDS
        shapes = {"w": (d, n_classes), "b": (n_classes,)}
    else:
        raise ValueError(kind)

    payload = codec.encode(sub, fields)
    header = WireHeader(kind=kind, cov_type=cov_type if kind == "gmm" else "",
                        d=int(d), K=int(K), n_classes=int(n_classes),
                        counts=tuple(int(c) for c in counts),
                        dtype=codec.dtype)
    decoded_sub = codec.decode(payload, shapes, fields)
    if kind == "gmm":
        C = n_classes
        decoded = {
            "pi": np.full((C, K), 1.0 / K, np.float32),
            "mu": np.zeros((C, K, d), np.float32),
            "cov": np.zeros((C,) + params["cov"].shape[1:], np.float32),
        }
        decoded["pi"][present] = decoded_sub["pi"]
        decoded["mu"][present] = decoded_sub["mu"]
        decoded["cov"][present] = _unpack_cov(decoded_sub["cov"], cov_type, d)
    else:
        decoded = decoded_sub
    decoded = {k: jnp.asarray(v) for k, v in decoded.items()}
    return ClientMessage(params=decoded,
                         logliks=tuple(float(v) for v in lls),
                         header=header, payload=payload)


def decode_payload(header: WireHeader, payload: bytes
                   ) -> Tuple[Optional[Dict[str, np.ndarray]],
                              Optional[str]]:
    """Validating wire → params path: re-derive the full ``(C, …)`` f32
    parameter stack from what actually crossed the wire.

    Returns ``(params, None)`` on a clean payload; ``(None, reason)``
    when the payload can't be decoded at all (bad schema / length);
    ``(params, reason)`` when it decodes but carries non-finite scalars
    (the caller sees both the poison and the diagnosis).  This is the
    receiver-side inverse of :func:`encode_message` and the decode path
    ``resilience.validate_message`` gates on — never raises.
    """
    if header.kind != "gmm":
        return None, f"bad_header: kind={header.kind!r} — expected 'gmm'"
    if header.dtype not in _WIRE_DTYPES:
        return None, f"bad_header: unknown wire dtype {header.dtype!r}"
    if header.cov_type not in G.COV_TYPES:
        return None, f"bad_header: cov_type={header.cov_type!r}"
    codec = QuantizedCodec(header.dtype)
    C, K, d = header.n_classes, header.K, header.d
    present = np.asarray(header.present, np.int64)
    Cp = len(present)
    shapes = {"pi": (Cp, K), "mu": (Cp, K, d),
              "cov": _packed_cov_shape(header.cov_type, Cp, K, d)}
    sub, err = codec.decode_checked(payload, shapes, _GMM_FIELDS)
    if sub is None:
        return None, err
    cov_full = _unpack_cov(sub["cov"], header.cov_type, d)
    decoded = {
        "pi": np.full((C, K), 1.0 / K, np.float32),
        "mu": np.zeros((C, K, d), np.float32),
        "cov": np.zeros((C,) + cov_full.shape[1:], np.float32),
    }
    decoded["pi"][present] = sub["pi"]
    decoded["mu"][present] = sub["mu"]
    decoded["cov"][present] = cov_full
    return decoded, err


def stack_messages(messages: Sequence[ClientMessage]) -> Dict[str, jax.Array]:
    """Homogeneous messages → the server's stacked ``(M, C, K, …)`` batch."""
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[m.params for m in messages])


def messages_from_wire(wire: Dict[str, jax.Array], counts, cov_type: str,
                       n_classes: int, codec: QuantizedCodec,
                       logliks=None, validate: bool = False):
    """Replicated mesh wire pytree → per-client :class:`ClientMessage` list.

    ``wire`` is what ``core.distributed.fedpft_transfer``'s all_gather left
    on every shard: ``gmm.pack_wire``'s bf16 stacked ``(I, C, K, …)``
    layout, full covs tril-packed.  Because the mesh path and the codec
    share ONE layout contract (``gmm.WIRE_FIELDS`` / ``gmm.tril_pack``),
    this is just ``gmm.unpack_wire`` followed by the same
    :func:`encode_message` a host client runs — with a bf16 codec each
    present class's payload scalars are bit-identical to what crossed the
    mesh.  ``comm_bytes`` keeps the host codec's semantics (Eqs. 9-11
    over PRESENT classes); the padded collective also carries absent
    classes' placeholder params — ``run_sharded`` reports that total
    separately as ``info["mesh_wire_bytes"]``.

    ``validate=True`` is the mesh path's quarantine gate (DESIGN.md §13):
    each decoded client whose parameters carry NaN/Inf is turned into a
    structured :class:`~repro.fl.resilience.Rejection` instead of a
    message, and the return becomes ``(messages, rejections)`` — byte
    accounting uses the comm bytes the client's present classes *would*
    have occupied on the host wire.
    """
    from repro.fl import resilience as RS   # local: resilience ← api cycle
    counts = np.asarray(jax.device_get(counts)).astype(np.int64)
    I = counts.shape[0]
    d = int(wire["mu"].shape[-1])
    unpacked = G.unpack_wire({k: np.asarray(jax.device_get(v))
                              for k, v in wire.items()}, cov_type, d)
    if logliks is None:
        logliks = np.zeros((I, n_classes), np.float32)
    messages: List[ClientMessage] = []
    rejections: List["RS.Rejection"] = []
    for i in range(I):
        params = {k: np.asarray(v[i], np.float32)
                  for k, v in unpacked.items()}
        if validate:
            present = np.flatnonzero(counts[i] > 0)
            bad = G.nonfinite_fields(
                {k: params[k][present] for k in _GMM_FIELDS})
            if bad:
                K = params["mu"].shape[-2]
                rejections.append(RS.Rejection(
                    client_id=i, reason="non_finite",
                    detail=f"mesh wire fields {bad} carry NaN/Inf",
                    comm_bytes=G.comm_bytes(cov_type, d, K, len(present),
                                            codec.bytes_per_scalar)))
                continue
        messages.append(encode_message(
            params, counts[i], np.asarray(logliks)[i], kind="gmm",
            cov_type=cov_type, n_classes=n_classes, codec=codec))
    if validate:
        return messages, rejections
    return messages


# ---------------------------------------------------------------------------
# planned server-side synthesis — one jitted sample per count bucket
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("S", "cov_type"))
def _sample_stacked(key, slot_ids, pi, mu, cov, S: int,
                    cov_type: str) -> jax.Array:
    """Draw S samples from every mixture in a flat (G, K, …) stack → (G, S, d).

    Keys are folded per *global* mixture slot id — distinct, deterministic
    draws for every (client, class) pair (the v1 loop re-split from one key
    and correlated clients; see ISSUE 1).  ``slot_ids`` carries the ids so
    a bucket of the planner folds the same keys as a monolithic dispatch.
    """
    d = mu.shape[-1]
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(slot_ids)
    # ONE sampler primitive (gmm.sampling_factor / colored_noise) shared
    # with the fused in-scan path — the Gaussian transform cannot drift
    # between the materializing and zero-materialization server phases
    fac = G.sampling_factor(cov, cov_type)                     # (G, K, …)

    def one(k, p, m, f):
        kc, kn = jax.random.split(k)
        logits = jnp.log(jnp.clip(p.astype(jnp.float32), 1e-20))
        comp = jax.random.categorical(kc, logits, shape=(S,))
        eps = jax.random.normal(kn, (S, d), jnp.float32)
        return m.astype(jnp.float32)[comp] + G.colored_noise(
            f[comp], eps, cov_type)

    return jax.vmap(one)(keys, pi, mu, fac)


def fused_slot_stack(batch: Dict[str, jax.Array], counts,
                     samples_per_class: Optional[int] = None):
    """Gather the planner's :class:`~repro.fl.planner.SlotTable` rows from
    a stacked ``(M, C, K, …)`` GMM batch → the flat ``(G, K, …)`` slot
    stack the fused head trainer consumes.

    THE construction of the zero-materialization server phase's input —
    ``FedSession`` (via :meth:`FedSession._fused_slot_stack`), the
    ``head_bench`` A/B, and the equivalence tests all build it here, so
    the layout (ascending global slot ids, labels = slot % C) cannot
    drift between them.  Returns ``(stack, slot_labels, slot_counts,
    plan)`` ready for ``core.head.train_head_from_gmms``.
    """
    counts = np.asarray(jax.device_get(counts), np.int64)
    if counts.ndim == 1:
        counts = counts[None]
        batch = jax.tree.map(lambda a: jnp.asarray(a)[None], batch)
    M, C = counts.shape
    plan = P.plan_synthesis(counts, samples_per_class)
    table = plan.slot_table
    flat = jax.tree.map(
        lambda a: jnp.asarray(a).reshape((M * C,) + a.shape[2:]), batch)
    slots = jnp.asarray(table.slots)
    stack = {k: flat[k][slots] for k in _GMM_FIELDS}
    labels = jnp.asarray((table.slots % C).astype(np.int32))
    return stack, labels, jnp.asarray(table.counts), plan


def synthesize_groups(key, items, samples_per_class: Optional[int] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Pooled synthesis over a possibly-heterogeneous cohort.

    ``items``: sequence of ``(params, counts, cov_type)`` per client.
    Clients with matching (cov_type, param shapes) stack into one group —
    one :func:`plan_synthesis` plan per group, one jitted sample per count
    bucket; a mixed-K/cov cohort (paper §6.3) gets one plan per family.
    The fold_in per group keeps draws deterministic in sorted-group order.
    Returns the pooled ``(N, d)`` features and ``(N,)`` labels.
    """
    groups: Dict[Tuple, List] = {}
    for params, counts, cov_type in items:
        sig = (cov_type,) + tuple(np.shape(params[f]) for f in _GMM_FIELDS)
        groups.setdefault(sig, []).append((params, counts))
    chunks = []
    for gi, (sig, members) in enumerate(sorted(groups.items())):
        batch = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[p for p, _ in members])
        counts = np.stack([np.asarray(jax.device_get(c)) for _, c in
                           members])
        ch, _ = synthesize_chunks(jax.random.fold_in(key, gi), batch,
                                  counts, sig[0], samples_per_class)
        chunks.extend(ch)
    return (jnp.concatenate([f for f, _ in chunks]),
            jnp.concatenate([y for _, y in chunks]))


def synthesize_chunks(key, batch: Dict[str, jax.Array], counts,
                      cov_type: str,
                      samples_per_class: Optional[int] = None,
                      policy: str = "pow2",
                      plan: Optional[P.SynthesisPlan] = None
                      ) -> Tuple[List[Tuple[jax.Array, jax.Array]],
                                 P.SynthesisPlan]:
    """Algorithm 1, lines 13-16, executed bucket-by-bucket.

    ``batch``: pi (M,C,K), mu (M,C,K,d), cov (M,C,K,…) — or the unstacked
    single-client (C,K,…) layout.  ``counts``: (M,C) sample counts; class
    slots with 0 are never emitted.  The count-stratified plan
    (:mod:`repro.fl.planner`) groups slots into power-of-two buckets; each
    bucket is ONE ``_sample_stacked`` call at the bucket's padded S,
    compacted host-side — so peak memory is O(largest bucket's padded
    block) and total draws are ≤ 2·Σcounts under any skew, vs the old
    monolithic dispatch's M·C·max(counts) (``policy="single"``, kept for
    benchmarks/synthesize_bench.py).

    Per-slot sampling keys fold on *global* slot ids, so no two slots
    ever share a key and a slot's key does not depend on its bucket
    assignment.  (The realized draws DO depend on the bucket's padded S —
    ``"pow2"`` and ``"single"`` agree in distribution and in per-slot
    counts/labels, not bitwise.)  Returns
    ``(chunks, plan)``; chunks is a list of compacted ``(feats (n, d),
    labels (n,))`` pairs in ascending-bucket order, and is never empty —
    an all-zero cohort yields one ``(0, d)`` chunk.
    """
    counts = np.asarray(jax.device_get(counts), np.int64)
    if counts.ndim == 1:
        counts = counts[None]
        batch = jax.tree.map(lambda a: jnp.asarray(a)[None], batch)
    M, C = counts.shape
    if plan is None:
        plan = P.plan_synthesis(counts, samples_per_class, policy=policy)
    elif (plan.M, plan.C) != (M, C):
        # a stale plan would gather wrong slots silently (jax clamps
        # out-of-range indices) — refuse instead
        raise ValueError(f"plan was built for a ({plan.M}, {plan.C}) "
                         f"cohort, counts are ({M}, {C})")
    d = batch["mu"].shape[-1]
    if not plan.buckets:
        return [(jnp.zeros((0, d), jnp.float32),
                 jnp.zeros((0,), jnp.int32))], plan

    flat = jax.tree.map(
        lambda a: jnp.asarray(a).reshape((M * C,) + a.shape[2:]), batch)
    chunks = []
    for b in plan.buckets:
        slots = jnp.asarray(b.slots)
        stacks = (flat["pi"][slots], flat["mu"][slots], flat["cov"][slots])
        # the shared key is deliberate: _sample_stacked folds it per
        # GLOBAL slot id, so draws are bucket-partition-invariant and
        # never collide across buckets (slots are disjoint)
        samples = _sample_stacked(key, slots, *stacks,  # lint: disable=KEY-CHAIN
                                  b.S, cov_type)               # (G_b, S, d)
        # compact away the padding rows host-side: one gather per bucket
        keep = np.arange(b.S)[None, :] < b.n_eff[:, None]      # (G_b, S)
        idx = np.flatnonzero(keep)
        labels = np.repeat((b.slots % C).astype(np.int32), b.S)[idx]
        feats = samples.reshape(len(b.slots) * b.S, d)[jnp.asarray(idx)]
        chunks.append((feats, jnp.asarray(labels)))
    return chunks, plan


def synthesize_batched(key, batch: Dict[str, jax.Array], counts,
                       cov_type: str,
                       samples_per_class: Optional[int] = None,
                       policy: str = "pow2"
                       ) -> Tuple[jax.Array, jax.Array]:
    """Pooled view of :func:`synthesize_chunks` — same plan, same draws.

    Returns the pooled (N, d) synthetic features and (N,) labels,
    N = Σ counts (or M·C_present·samples_per_class).
    """
    chunks, _ = synthesize_chunks(key, batch, counts, cov_type,
                                  samples_per_class, policy=policy)
    return (jnp.concatenate([f for f, _ in chunks]),
            jnp.concatenate([y for _, y in chunks]))


def synthesize_looped(key, batch: Dict, counts, cov_type: str,
                      samples_per_class: Optional[int] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Reference per-(client, class) Python loop — the pre-redesign server
    path, kept for the equivalence tests and ``benchmarks/synthesize_bench``.
    """
    counts = np.asarray(jax.device_get(counts), np.int64)
    if counts.ndim == 1:
        counts = counts[None]
        batch = jax.tree.map(lambda a: a[None], batch)
    M, C = counts.shape
    feats, labels = [], []
    for m in range(M):
        for c in range(C):
            n = int(counts[m, c])
            if samples_per_class is not None and n > 0:
                n = samples_per_class
            if n <= 0:
                continue
            g = jax.tree.map(lambda a: jnp.asarray(a)[m, c], batch)
            k = jax.random.fold_in(key, m * C + c)
            feats.append(G.sample(k, g, n, cov_type))
            labels.append(jnp.full((n,), c, jnp.int32))
    if not feats:
        d = np.asarray(batch["mu"]).shape[-1]
        return jnp.zeros((0, d), jnp.float32), jnp.zeros((0,), jnp.int32)
    return jnp.concatenate(feats), jnp.concatenate(labels)


# ---------------------------------------------------------------------------
# summarizers — the pluggable "what goes on the wire" slot
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GMMSummarizer:
    """The paper's summary: one GMM per present class (Algorithm 1, l. 5-10).

    The per-class EM stack runs as ONE batched fit
    (``gmm.fit_classwise_gmms`` → ``fit_gmm_batch``): the diag/spher
    E-step of all C fits is a single ``kernels.ops.gmm_estep_fused``
    call per iteration — the compiled Pallas kernel on a TPU, the same
    kernel interpreted on the CPU (DESIGN.md §8).
    """
    gmm: G.GMMConfig = G.GMMConfig()

    kind = "gmm"

    @property
    def cov_type(self) -> str:
        return self.gmm.cov_type

    def summarize(self, key, feats, labels, n_classes: int):
        gmms, counts, lls = G.fit_classwise_gmms(key, feats, labels,
                                                 n_classes, self.gmm)
        return gmms, counts, lls


@dataclasses.dataclass(frozen=True)
class HeadSummarizer:
    """Head-level summary for the one-shot baselines (AVG/Ensemble/FedBE):
    the client ships a locally-trained linear head instead of GMMs — same
    message schema, same codec, different aggregation."""
    n_steps: int = 150
    lr: float = 3e-3

    kind = "head"
    cov_type = ""

    def summarize(self, key, feats, labels, n_classes: int):
        from repro.fl import baselines as FB
        k_init, k_train = jax.random.split(key)
        # drop padding rows (label −1): take_along_axis would wrap them to
        # the last class and train the head on zero-feature rows
        keep = np.flatnonzero(np.asarray(jax.device_get(labels)) >= 0)
        if len(keep) < np.shape(labels)[0]:
            feats, labels = feats[keep], labels[keep]
        d = int(feats.shape[1])
        head = FB.local_train(k_train, H.init_head(k_init, d, n_classes),
                              feats, labels, n_classes,
                              n_steps=self.n_steps, lr=self.lr)
        counts = jnp.sum(jax.nn.one_hot(labels, n_classes), axis=0)
        return head, counts, jnp.zeros((n_classes,), jnp.float32)


# ---------------------------------------------------------------------------
# topologies
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SessionResult:
    """What a federation round produced."""
    model: Any                     # global head (star) / per-client heads
    info: Dict
    messages: List[ClientMessage]


def _fault_stats() -> Dict:
    """Mutable client-phase retry ledger (one per round) — what lands in
    ``info["faults"]`` next to the broker's verdict accounting."""
    return {"attempts": 0, "retries": 0, "backoff_s": 0.0, "failed": []}


def _merge_fault_info(info: Dict, acct: Dict,
                      expected: Optional[int] = None) -> None:
    """Fold broker accounting into ``info["faults"]``: coverage fraction
    against the expected cohort and the ``degraded`` flag (any loss —
    missing, quarantined, late, or after-close — marks the round partial).
    Preserves client-phase retry stats already present under "faults"."""
    if expected is None:
        expected = acct["clients_seen"]
    coverage = acct["admitted"] / expected if expected else 1.0
    degraded = (acct["admitted"] < expected or acct["quarantined"] > 0
                or acct["late"] > 0 or acct["closed"] > 0)
    faults = info.setdefault("faults", {})
    faults.update(degraded=bool(degraded), coverage=float(coverage),
                  expected_clients=int(expected))


@dataclasses.dataclass(frozen=True)
class Star:
    """Clients → server, one shot (Algorithm 1)."""
    name = "star"

    def run(self, key, session: "FedSession", client_datasets
            ) -> SessionResult:
        keys = jax.random.split(key, len(client_datasets) + 1)
        stats = _fault_stats()
        messages = []
        for i, (k, (f, y)) in enumerate(zip(keys[1:], client_datasets)):
            msg = session._client_attempt(k, f, y, i, stats)
            if msg is None:
                # no broker in a non-streaming round — there is no ledger
                # to absorb a lost client, so exhaustion is fatal here
                raise RS.TransientClientError(
                    f"client {i} still failing after "
                    f"{session.resilience.max_retries + 1} attempts — "
                    "use FedSession(ingest=...) to degrade instead")
            messages.append(msg)
        result = session.server_aggregate(keys[0], messages)
        if stats["retries"]:
            result.info.setdefault("faults", {}).update(
                attempts=stats["attempts"], retries=stats["retries"],
                backoff_s=stats["backoff_s"])
        return result


@dataclasses.dataclass(frozen=True)
class Chain:
    """Linear topology (§4.2, Fig. 5): client 1 → 2 → … → M.  Each client
    decodes the received message, samples synthetic features from it, unions
    them with its local data, re-fits, re-encodes, and passes on."""
    laps: int = 1
    name = "chain"

    def run(self, key, session: "FedSession", client_datasets
            ) -> SessionResult:
        M = len(client_datasets)
        order = list(range(M)) * self.laps
        keys = jax.random.split(key, len(order))
        received = None
        messages, infos = [], []
        for k, i in zip(keys, order):
            f, y = client_datasets[i]
            msg, info = session.chain_step(k, f, y, i, received)
            messages.append(msg)
            infos.append(info)
            received = msg
        comm = sum(m.comm_bytes for m in messages)
        return SessionResult(model=infos[-1]["head"],
                             info={"comm_bytes": comm, "per_client": infos},
                             messages=messages)


@dataclasses.dataclass(frozen=True)
class Ring(Chain):
    """Chain with wraparound: after ``laps`` passes every client (including
    the first) has refit on the accumulated global knowledge."""
    laps: int = 2
    name = "ring"


# ---------------------------------------------------------------------------
# FedSession — the orchestrator
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FedSession:
    """One federation instance: summarizer × codec × topology (× DP).

    >>> sess = FedSession(n_classes=10,
    ...                   summarizer=GMMSummarizer(G.GMMConfig(5, "diag")))
    >>> result = sess.run(key, clients)        # doctest: +SKIP
    >>> result.info["comm_bytes"] == sum(len(m.payload)
    ...                                  for m in result.messages)
    """
    n_classes: int
    summarizer: Any = GMMSummarizer()
    codec: QuantizedCodec = QuantizedCodec("bfloat16")
    topology: Any = Star()
    head: H.HeadConfig = H.HeadConfig()
    normalize_features: bool = False
    dp: Optional[DP.DPConfig] = None
    samples_per_class: Optional[int] = None
    aggregate: str = "synthesize"  # "synthesize" | "avg" | "ensemble" | "fedbe"
    client_summarizers: Optional[Tuple[Any, ...]] = None  # heterogeneous K/cov
    min_class_count: int = 0       # don't transmit classes below this count
    # -- AOT round-program cache (DESIGN.md §11) ----------------------------
    #   a launch.aot_cache.ProgramCache: the fused server phase runs as an
    #   ahead-of-time compiled round program, slot stacks padded to the
    #   cache's canonical signature grid (bit-identical heads — count-0
    #   identity pads are no-ops).  One cache instance serves the host,
    #   mesh, and ingest paths; hit/miss + amortized latency land in
    #   info["compile"].  Heterogeneous cohorts (mixed K/cov, §6.3) bypass
    #   it via the pooled fallback.
    program_cache: Optional[Any] = None
    # -- streaming ingestion (DESIGN.md §9) ---------------------------------
    #   IngestConfig routes the server phase through fl.ingest: arriving
    #   messages fold into a fixed-capacity reservoir chunk-at-a-time, so
    #   peak server memory and the fused scan's compile key are independent
    #   of the cohort size M.  The bytes of the reservoir stack sent to the
    #   device land in info["staged_bytes"].
    ingest: Optional[IG.IngestConfig] = None
    # -- fault policy (DESIGN.md §13) ---------------------------------------
    #   ResilienceConfig arms the wire-level quarantine gate on the
    #   host/mesh aggregate paths (the streaming broker has its own
    #   IngestConfig.validate) and the client-phase retry contract:
    #   TransientClientError replays the attempt up to max_retries times
    #   with deterministic exponential backoff on an injected clock.
    #   info["faults"] records attempts/retries/degraded/coverage.
    resilience: Optional[RS.ResilienceConfig] = None
    # -- mesh execution mode (DESIGN.md §5) ---------------------------------
    mesh: Any = None               # jax Mesh with a "data" axis, or None
    shards: Optional[int] = None   # convenience: make_sim_mesh(shards)
    transfer_seed: int = 0         # per-client PRNG base for the mesh round

    # -- plumbing -----------------------------------------------------------

    def summarizer_for(self, i: int):
        if self.client_summarizers is not None:
            return self.client_summarizers[i]
        return self.summarizer

    def _normalize(self, feats):
        if not self.normalize_features:
            return feats
        n = jnp.linalg.norm(feats, axis=-1, keepdims=True)
        return feats / jnp.maximum(n, 1.0)

    # -- client side --------------------------------------------------------

    def client_update(self, key, feats, labels, i: int = 0) -> ClientMessage:
        """Summarize → (optionally privatize) → encode."""
        summ = self.summarizer_for(i)
        with obs.span("client.fit", client=i):
            k_fit, k_dp = jax.random.split(key)
            feats = self._normalize(feats)
            params, counts, lls = summ.summarize(k_fit, feats, labels,
                                                 self.n_classes)
            if self.min_class_count and summ.kind == "gmm":
                counts = jnp.where(counts >= self.min_class_count, counts,
                                   0)
            if self.dp is not None:
                assert summ.kind == "gmm" and summ.cov_type == "full" \
                    and params["mu"].shape[-2] == 1, \
                    "Theorem 4.1 requires K=1 full-covariance summaries"
                params = DP.privatize_classwise(k_dp, params, counts,
                                                self.dp)
        return encode_message(params, counts, lls, kind=summ.kind,
                              cov_type=summ.cov_type,
                              n_classes=self.n_classes, codec=self.codec)

    def _client_attempt(self, key, feats, labels, i: int, stats: Dict,
                        client_fn=None, advance=None):
        """One client's message under the session's retry contract.

        With ``resilience`` set, :class:`~repro.fl.resilience
        .TransientClientError` replays the attempt (same key — the
        attempt is a pure function of it) up to ``max_retries`` times,
        backoff accounted on ``advance``.  Returns None when the client
        exhausted its attempts; the caller decides whether that drops
        the client (streaming/chaos rounds) or fails the round (Star).
        ``client_fn`` lets the chaos path wrap ``client_update`` in a
        fault injector.
        """
        fn = self.client_update if client_fn is None else client_fn
        if self.resilience is None:
            stats["attempts"] += 1
            return fn(key, feats, labels, i)
        ok, msg, attempts, backoff = RS.call_with_retry(
            lambda: fn(key, feats, labels, i), self.resilience,
            advance=advance)
        stats["attempts"] += attempts
        stats["retries"] += attempts - 1
        stats["backoff_s"] += backoff
        if not ok:
            stats["failed"].append(i)
            return None
        return msg

    def chain_step(self, key, feats, labels, i: int,
                   received: Optional[ClientMessage]
                   ) -> Tuple[ClientMessage, Dict]:
        """One client's turn in a Chain/Ring pass."""
        if self.dp is not None:
            # Theorem 4.1's accounting covers one summary of one client's
            # data; a chain message summarizes a union that includes other
            # clients' synthetic samples. Refuse rather than emit messages
            # with an unaccounted (and therefore void) privacy guarantee.
            raise NotImplementedError(
                "DP composition is only supported for the Star topology")
        if self.summarizer_for(i).kind != "gmm":
            # a head summary can't be "sampled and unioned"; refuse instead
            # of silently dropping every received message
            raise NotImplementedError(
                "Chain/Ring topologies require a GMM summarizer")
        k_sample, k_fit, k_head = jax.random.split(key, 3)
        feats = self._normalize(feats)
        if received is not None and received.header.kind == "gmm":
            syn_f, syn_y = synthesize_batched(
                k_sample, received.params, received.counts,
                received.header.cov_type)
            if syn_f.shape[0]:
                feats = jnp.concatenate([feats, syn_f], axis=0)
                labels = jnp.concatenate([labels, syn_y], axis=0)
        summ = self.summarizer_for(i)
        params, counts, lls = summ.summarize(k_fit, feats, labels,
                                             self.n_classes)
        if self.min_class_count and summ.kind == "gmm":
            counts = jnp.where(counts >= self.min_class_count, counts, 0)
        msg = encode_message(params, counts, lls, kind=summ.kind,
                             cov_type=summ.cov_type,
                             n_classes=self.n_classes, codec=self.codec)
        head_params, _ = H.train_head(k_head, feats, labels, self.n_classes,
                                      self.head)
        return msg, {"head": head_params, "n_train": int(feats.shape[0])}

    # -- server side --------------------------------------------------------

    def _fused_slot_stack(self, messages: Sequence[ClientMessage]):
        """``(slot stack, plan)`` for :meth:`_fused_round` — the stack as
        ``(pi, mu, cov, slot_labels, slot_counts)`` — or None if the cohort
        is heterogeneous (mixed K / cov family, §6.3) and can't stack into
        one (G, K, …) tensor."""
        sigs = {(m.header.cov_type,)
                + tuple(np.shape(m.params[f]) for f in _GMM_FIELDS)
                for m in messages}
        if len(sigs) > 1:
            return None
        stack, labels, counts, plan = fused_slot_stack(
            stack_messages(messages), np.stack([m.counts for m in messages]),
            self.samples_per_class)
        return (stack["pi"], stack["mu"], stack["cov"], labels, counts), plan

    def _empty_cohort_result(self, k_head, info: Dict, messages,
                             d: Optional[int] = None) -> SessionResult:
        """min_class_count (or an all-empty cohort) filtered every class:
        return a cleanly-initialized head instead of crashing train_head
        on a 0-row pool.  ``d`` overrides the feature dim for callers that
        discarded their messages (the streaming run loop)."""
        if d is None:
            d = messages[0].header.d
        info.update(synthetic_feats=jnp.zeros((0, d), jnp.float32),
                    synthetic_labels=jnp.zeros((0,), jnp.int32),
                    head_losses=jnp.zeros((0,), jnp.float32),
                    empty_cohort=True)
        return SessionResult(model=H.init_head(k_head, d, self.n_classes),
                             info=info, messages=list(messages))

    def _fused_round(self, k_head, make_stack, cov_type: str, info: Dict,
                     messages: Sequence[ClientMessage] = (),
                     mesh=None) -> SessionResult:
        """THE server phase (DESIGN.md §2, §11): train the head straight
        from a flat slot stack ``(pi, mu, cov, slot_labels, slot_counts)``
        — the pooled ``(N, d)`` tensor never exists.  ``make_stack()``
        returns the stack; it runs inside the ``round.stage`` span, so the
        span covers unpacking the reservoir.

        Every GMM route ends here: the message list (host and mesh) with
        :meth:`_fused_slot_stack`'s compacted stack, the ingest session
        and :meth:`aggregate_from_broker` with the reservoir's padded one.
        With a ``program_cache`` the stack is padded to the cache's
        canonical signature (leading ``gmm.identity_gmm`` rows of count 0
        — exact no-ops, so the head is bit-identical to the exact-shape
        run) and the AOT executable runs; without one,
        ``head.train_head_from_gmms`` runs at the exact shape.  Under a
        mesh every operand is pinned to the replicated layout: same inputs
        and the same RNG on every shard train identical heads, and the
        layout matches the executable's AOT input shardings (DESIGN.md
        §5).  A stack with no draw mass (every class filtered) returns the
        freshly-initialized head.  Fills ``info["staged_bytes"]``,
        ``info["head_losses"]`` and, when cached, ``info["compile"]``.
        """
        cache = self.program_cache
        with obs.span("round.stage"):
            stack = make_stack()
            M, K, d = (int(n) for n in np.shape(stack[1]))
            if not np.any(np.asarray(jax.device_get(stack[4])) > 0):
                return self._empty_cohort_result(k_head, info, messages,
                                                 d=d)
            sig = FR.CohortSignature(M=M, C=self.n_classes, K=K, d=d,
                                     cov_type=cov_type)
            if cache is not None:
                canon = cache.canonical(sig)
                stack = FR.pad_slots(*stack, sig, canon)
            info["staged_bytes"] = sum(a.nbytes for a in stack)
            staged = tuple(map(jnp.asarray, stack))
            if mesh is not None:
                repl = jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec())
                k_head = jax.device_put(k_head, repl)
                staged = tuple(jax.device_put(a, repl) for a in staged)
            pi, mu, cov, slot_labels, slot_counts = jax.block_until_ready(
                staged)
        if cache is None:
            with obs.span("round.program"):
                head_params, losses = H.train_head_from_gmms(
                    k_head, pi, mu, cov, slot_labels, slot_counts,
                    self.n_classes, self.head, cov_type)
                jax.block_until_ready(head_params)
        else:
            hits0 = cache.hits
            prog = cache.get(sig, self.head, mesh=mesh)
            with obs.span("round.program") as run:
                head_params, losses = prog(k_head, pi, mu, cov, slot_counts,
                                           slot_labels)
                jax.block_until_ready(head_params)
            run_us = run.elapsed_ns / 1e3
            info["compile"] = {
                "hit": cache.hits > hits0, "aot": prog.aot,
                "signature": dataclasses.astuple(sig),
                "canonical": dataclasses.astuple(canon),
                "compile_us": prog.compile_us, "run_us": run_us,
                # compile cost spread over every round the entry has
                # served — the multi-tenant metric compile_bench tracks
                "amortized_us": prog.compile_us / max(prog.uses, 1) + run_us,
                "cache": cache.stats(),
            }
        info["head_losses"] = losses
        return SessionResult(model=head_params, info=info,
                             messages=list(messages))

    def _pooled_round(self, k_syn, k_head,
                      messages: Sequence[ClientMessage],
                      info: Dict) -> SessionResult:
        """The heterogeneous-cohort fallback (mixed K / cov family, paper
        §6.3): no one slot stack holds the cohort, so the pool is
        synthesized per schema group (:func:`synthesize_groups`) and the
        head trains on it.  Mesh cohorts share one summarizer, so they
        never land here."""
        info["synthesis"] = "pooled"
        info["synthesis_fallback"] = "heterogeneous cohort"
        feats, labels = synthesize_groups(
            k_syn, [(m.params, m.counts, m.header.cov_type)
                    for m in messages], self.samples_per_class)
        if feats.shape[0] == 0:
            return self._empty_cohort_result(k_head, info, messages)
        head_params, losses = H.train_head(k_head, feats, labels,
                                           self.n_classes, self.head)
        info.update(synthetic_feats=feats, synthetic_labels=labels,
                    head_losses=losses)
        return SessionResult(model=head_params, info=info,
                             messages=list(messages))

    def _close_broker(self, key, broker, info: Dict,
                      expected: Optional[int],
                      messages: Sequence[ClientMessage] = (),
                      mesh=None) -> SessionResult:
        """Seal ``broker`` and train the head from its reservoir — the key
        plumbing (``_, k_head = split(key)``, the fused branch's ``(k_syn,
        k_head)`` split, so heads are bit-identical either way), byte
        accounting and fault info every broker route shares."""
        state = broker.close()
        _, k_head = jax.random.split(key)
        info = {"synthesis": "fused", **info}
        acct = broker.accounting()
        info["ingest"] = acct
        info.setdefault("comm_bytes", acct["sent_bytes"])
        _merge_fault_info(info, acct, expected=expected)
        if state is None:
            return self._empty_cohort_result(k_head, info, messages,
                                             d=broker.header_d)
        return self._fused_round(k_head, state.padded_stack,
                                 state.cov_type, info, messages, mesh=mesh)

    def _ingest_aggregate(self, key, messages: Sequence[ClientMessage],
                          info: Dict, mesh=None) -> SessionResult:
        """Server phase through the streaming broker (DESIGN.md §9).

        The message list stands in for the arrival stream — position is
        the client id, matching the Star round's enumeration.  Admission,
        byte accounting, and chunked folding all run exactly as in the
        streaming loop, so this path (host or mesh) and
        :meth:`_run_streaming` share one state machine.
        """
        broker = IG.IngestBroker(self.ingest, self.n_classes,
                                 samples_per_class=self.samples_per_class)
        for i, m in enumerate(messages):
            broker.submit(i, m)
        return self._close_broker(key, broker, info, len(messages),
                                  messages, mesh=mesh)

    def aggregate_from_broker(self, key, broker,
                              info: Optional[Dict] = None,
                              expected_clients: Optional[int] = None
                              ) -> SessionResult:
        """Close an externally-owned :class:`~repro.fl.ingest.IngestBroker`
        and train the head from its reservoir.

        The serving loop (``serve.service.FedPFTService``) feeds wire
        messages into a broker as clients submit them; at round close it
        hands the broker here.  Key plumbing matches
        :meth:`_ingest_aggregate` / :meth:`_run_streaming` — ``_, k_head =
        split(key)`` — so a service round is bit-identical to the offline
        session given the same admitted cohort and the same ``key``.
        Partial rounds (deadline/quarantine losses) degrade instead of
        failing: ``info["faults"]`` reports the ``degraded`` flag and the
        coverage fraction against ``expected_clients`` (default: distinct
        client ids the broker saw).
        """
        return self._close_broker(key, broker, info or {}, expected_clients)

    def server_aggregate(self, key, messages: Sequence[ClientMessage],
                         mesh=None) -> SessionResult:
        if not messages:
            raise ValueError("server_aggregate needs at least one message")
        comm = sum(m.comm_bytes for m in messages)
        info: Dict = {"comm_bytes": comm}
        kind = messages[0].header.kind
        if kind == "gmm" and self.ingest is not None:
            return self._ingest_aggregate(key, messages, info, mesh=mesh)
        if kind == "gmm":
            k_syn, k_head = jax.random.split(key)
            if self.resilience is not None and self.resilience.validate:
                # wire-level quarantine, host and mesh paths (§13): drop
                # malformed/non-finite messages with a structured record
                # instead of letting the fold/stack crash the round
                d0 = int(messages[0].header.d)
                kept, rejs = RS.partition_valid(messages, self.n_classes)
                if rejs:
                    info["quarantined"] = [dataclasses.asdict(r)
                                           for r in rejs]
                    info["quarantined_bytes"] = sum(r.comm_bytes
                                                    for r in rejs)
                    info["faults"] = {
                        "degraded": True,
                        "coverage": len(kept) / len(messages)}
                    if not kept:
                        return self._empty_cohort_result(k_head, info, [],
                                                         d=d0)
                    messages = kept
            fused = self._fused_slot_stack(messages)
            if fused is None:
                return self._pooled_round(k_syn, k_head, messages, info)
            stack, plan = fused
            info["synthesis"] = "fused"
            info["synthesis_plans"] = [plan]
            return self._fused_round(k_head, lambda: stack,
                                     messages[0].header.cov_type, info,
                                     messages, mesh=mesh)
        # head-level aggregation (one-shot baselines) — estimators match
        # the paper's: uniform AVG, FedBE with 10 posterior samples
        from repro.fl import baselines as FB
        heads = [m.params for m in messages]
        if self.aggregate == "avg":
            model: Any = FB.avg_heads(heads)
        elif self.aggregate == "ensemble":
            model = list(heads)
        elif self.aggregate == "fedbe":
            model = FB.fedbe(key, heads, n_samples=10)
        else:
            raise ValueError(self.aggregate)
        return SessionResult(model=model, info=info, messages=list(messages))

    # -- mesh execution mode (DESIGN.md §5) ---------------------------------

    def _resolve_mesh(self):
        if self.mesh is not None:
            n = DF.data_axis_size(self.mesh, where="FedSession")
            if self.shards is not None and self.shards != n:
                raise ValueError(
                    f"FedSession: mesh= is {n}-way on 'data' but shards="
                    f"{self.shards} — they disagree; pass one, or make "
                    "them match")
            return self.mesh
        if self.shards is None:
            raise ValueError(
                "FedSession: sharded execution needs mesh= (a jax Mesh "
                "with a 'data' axis) or shards=n (builds "
                "launch.mesh.make_sim_mesh(n) over the host's devices)")
        from repro.launch.mesh import make_sim_mesh
        return make_sim_mesh(self.shards)

    def _check_sharded_config(self, I: int, n_shards: int) -> None:
        """Every mesh-mode precondition, checked BEFORE any device work."""
        DF.validate_cohort(I, n_shards, where="FedSession(sharded)")
        if self.client_summarizers is not None:
            raise NotImplementedError(
                "FedSession(sharded): heterogeneous client_summarizers "
                "can't batch into one shard_map program — run the host "
                "Star path for mixed-K/cov cohorts (paper §6.3)")
        if self.summarizer.kind != "gmm":
            raise NotImplementedError(
                "FedSession(sharded): the mesh round fits GMM summaries "
                "(core.distributed.fedpft_transfer); head-summary "
                "baselines run on the host Star path")
        if self.dp is not None:
            raise NotImplementedError(
                "FedSession(sharded): the DP mechanism (Theorem 4.1) is "
                "applied host-side before encoding — run the host Star "
                "path with dp=, or privatize before calling run_sharded")
        if not isinstance(self.topology, Star):
            raise NotImplementedError(
                f"FedSession(sharded): the one-shot all_gather IS the Star "
                f"round; {self.topology.name!r} topologies are host-only")
        if self.codec.dtype != "bfloat16":
            raise ValueError(
                f"FedSession(sharded): the mesh wire is bf16 "
                f"(gmm.pack_wire) but the codec is {self.codec.dtype!r} — "
                "comm accounting would not match the collective. Use "
                "QuantizedCodec('bfloat16') or the host path for fp16/fp32 "
                "wire ablations")

    def run_sharded(self, key, feats: jax.Array, labels: jax.Array
                    ) -> SessionResult:
        """One-shot round as mesh collectives (DESIGN.md §5).

        ``feats``: (I, N, d) — I clients, N padded samples; ``labels``:
        (I, N) with −1 padding.  Client phase: each shard of the "data"
        axis fits its I/n_shards clients' classwise GMMs as ONE batched EM
        (per-client PRNG seeds offset by the shard's global client base,
        ``transfer_seed + i`` for client i) and ``all_gather``s the bf16
        wire pytree — that collective is the round.  Server phase: the
        replicated wire decodes through the SAME codec layout host clients
        use (:func:`messages_from_wire`), then the fused server phase
        (:meth:`_fused_round`) trains the head replicated on every shard.
        Results are shard-count invariant up to wire precision
        (tests/multidevice).
        """
        n_shards = self.shards if self.mesh is None and \
            self.shards is not None else None
        if n_shards is not None:
            # divisibility is checkable before building the mesh — a
            # too-small host should complain about XLA_FLAGS, not shapes
            DF.validate_cohort(feats.shape[0], n_shards,
                               where="FedSession(sharded)")
        mesh = self._resolve_mesh()
        self._check_sharded_config(feats.shape[0], mesh.shape["data"])
        feats = self._normalize(feats)
        wire, counts, lls = DF.fedpft_transfer(mesh, feats, labels,
                                               self.n_classes,
                                               self.summarizer.gmm,
                                               seed=self.transfer_seed)
        counts = np.asarray(jax.device_get(counts)).astype(np.int64)
        if self.min_class_count:
            counts = np.where(counts >= self.min_class_count, counts, 0)
        validate = self.resilience is not None and self.resilience.validate
        decoded = messages_from_wire(wire, counts,
                                     self.summarizer.cov_type,
                                     self.n_classes, self.codec,
                                     logliks=jax.device_get(lls),
                                     validate=validate)
        messages, wire_rejs = decoded if validate else (decoded, [])
        if not messages:
            # every client quarantined at the mesh wire: the empty-cohort
            # guard, with the same (k_syn, k_head) split as server_aggregate
            _, k_head = jax.random.split(key)
            info: Dict = {
                "comm_bytes": 0,
                "quarantined": [dataclasses.asdict(r) for r in wire_rejs],
                "quarantined_bytes": sum(r.comm_bytes for r in wire_rejs),
                "faults": {"degraded": True, "coverage": 0.0},
            }
            result = self._empty_cohort_result(k_head, info, [],
                                               d=int(feats.shape[-1]))
        else:
            result = self.server_aggregate(key, messages, mesh=mesh)
            if wire_rejs:
                result.info.setdefault(
                    "quarantined", []).extend(dataclasses.asdict(r)
                                              for r in wire_rejs)
                result.info["quarantined_bytes"] = (
                    result.info.get("quarantined_bytes", 0)
                    + sum(r.comm_bytes for r in wire_rejs))
                faults = result.info.setdefault("faults", {})
                faults["degraded"] = True
                faults["coverage"] = len(messages) / int(feats.shape[0])
        g = self.summarizer.gmm
        result.info.update(
            n_shards=int(mesh.shape["data"]),
            mesh_axes=tuple(mesh.axis_names),
            # what the collective itself moved: the full padded (I, C, …)
            # bf16 pytree — absent / min_class_count-filtered classes still
            # cross the mesh, unlike the host codec's present-class payloads
            # (comm_bytes)
            mesh_wire_bytes=DF.expected_wire_bytes(
                g.cov_type, feats.shape[-1], g.n_components,
                self.n_classes, feats.shape[0]))
        return result

    # -- streaming ingestion run (DESIGN.md §9) -----------------------------

    def _run_streaming(self, key, client_datasets) -> SessionResult:
        """The Star round with M as a streaming axis: each client's message
        is produced, submitted to the broker, and DISCARDED — the full
        message list never exists, so peak server memory is the broker's
        law (fixed-capacity state + one pending chunk) regardless of M.

        Key plumbing mirrors ``Star.run`` + ``server_aggregate`` exactly
        (per-client ``keys[1:]``, server ``keys[0]``, the ``(k_syn,
        k_head)`` split), so under capacity the returned head is
        bit-identical to the non-streaming fused session's.
        """
        if not isinstance(self.topology, Star):
            raise NotImplementedError(
                f"FedSession(ingest=...): the broker receives one-shot "
                f"Star messages; {self.topology.name!r} rounds are "
                "sequential relays with no cohort to stream — drop ingest=")
        if self.summarizer.kind != "gmm" or (
                self.client_summarizers is not None and any(
                    s.kind != "gmm" for s in self.client_summarizers)):
            raise NotImplementedError(
                "FedSession(ingest=...): streaming ingestion folds GMM "
                "summaries; head-summary baselines aggregate via the "
                "non-streaming path (aggregate=...)")
        if not client_datasets:
            raise ValueError("server_aggregate needs at least one message")
        keys = jax.random.split(key, len(client_datasets) + 1)
        broker = IG.IngestBroker(self.ingest, self.n_classes,
                                 samples_per_class=self.samples_per_class)
        comm = 0
        stats = _fault_stats()
        for i, (k, (f, y)) in enumerate(zip(keys[1:], client_datasets)):
            msg = self._client_attempt(k, f, y, i, stats)
            if msg is None:
                continue    # retries exhausted: lost at source, the
                #   broker's coverage fraction reports the gap
            comm += msg.comm_bytes
            broker.submit(i, msg)
            del msg
        info: Dict = {"comm_bytes": comm}
        if stats["retries"] or stats["failed"]:
            info["faults"] = {"attempts": stats["attempts"],
                              "retries": stats["retries"],
                              "backoff_s": stats["backoff_s"],
                              "failed_clients": stats["failed"]}
        return self.aggregate_from_broker(
            keys[0], broker, info=info,
            expected_clients=len(client_datasets))

    # -- chaos run (DESIGN.md §13) ------------------------------------------

    def _run_chaos(self, key, client_datasets, plan) -> SessionResult:
        """The streaming Star round under a :class:`~repro.fl.faults
        .FaultPlan`: produce every client's message (transient failures
        retried per the resilience contract), push the cohort through the
        plan's delivery schedule on a fake clock, and close the round on
        whatever the broker admitted.

        Key plumbing is :meth:`_run_streaming`'s exactly (per-client
        ``keys[1:]``, server ``keys[0]``), and retries replay the same
        per-client key — so the produced messages, and therefore the
        partial-round head, are bit-identical to an offline session fed
        the surviving (admitted) clients in any order.
        """
        from repro.fl import faults as FJ
        if self.ingest is None:
            raise ValueError(
                "FedSession.run(faults=...): chaos rounds stream through "
                "the broker — set ingest=IngestConfig(...) so losses "
                "degrade coverage instead of failing the round")
        if self.mesh is not None or self.shards is not None:
            raise NotImplementedError(
                "FedSession.run(faults=...): chaos injection wraps the "
                "host wire; the mesh round has no per-message delivery "
                "to perturb")
        if not isinstance(self.topology, Star):
            raise NotImplementedError(
                f"FedSession.run(faults=...): fault schedules target the "
                f"one-shot Star cohort; {self.topology.name!r} relays "
                "have no concurrent arrival stream")
        M = len(client_datasets)
        if not M:
            raise ValueError("server_aggregate needs at least one message")
        keys = jax.random.split(key, M + 1)
        stats = _fault_stats()
        produced: List[Tuple[int, ClientMessage]] = []
        for i, (k, (f, y)) in enumerate(zip(keys[1:], client_datasets)):
            fate = plan.fate(i)
            fn = None
            if fate.transient_fails:
                fn = FJ.flaky(self.client_update, fate.transient_fails)
            msg = self._client_attempt(k, f, y, i, stats, client_fn=fn)
            if msg is not None:
                produced.append((i, msg))
        deliveries = FJ.schedule(plan, produced)
        fake = {"t": 0.0}
        broker = IG.IngestBroker(self.ingest, self.n_classes,
                                 samples_per_class=self.samples_per_class,
                                 clock=lambda: fake["t"])
        for ev in deliveries:
            fake["t"] = max(fake["t"], ev.t)   # arrivals are monotonic
            broker.submit(ev.client_id, ev.message)
        info: Dict = {"faults": {
            "plan_seed": plan.seed,
            "attempts": stats["attempts"],
            "retries": stats["retries"],
            "backoff_s": stats["backoff_s"],
            "failed_clients": stats["failed"],
            "produced": len(produced),
            "delivered": len(deliveries),
            # the survivor set — an offline session fed exactly these
            # clients (same keys) reproduces this round's head bitwise
            "admitted_clients": list(broker.admitted_ids),
        }}
        return self.aggregate_from_broker(keys[0], broker, info=info,
                                          expected_clients=M)

    # -- entry point --------------------------------------------------------

    def run(self, key, client_datasets: Sequence[Tuple[jax.Array, jax.Array]],
            faults=None) -> SessionResult:
        if faults is not None:
            return self._run_chaos(key, client_datasets, faults)
        if self.mesh is not None or self.shards is not None:
            shapes = {(tuple(np.shape(f)), tuple(np.shape(y)))
                      for f, y in client_datasets}
            if len(shapes) != 1:
                raise ValueError(
                    f"FedSession(sharded): clients must share one "
                    f"(N, d) / (N,) feats/labels shape to stack into the "
                    f"mesh round, got {sorted(shapes)} — pad to a common N "
                    "with label −1 rows, or run the host path (mesh=None, "
                    "shards=None)")
            feats = jnp.stack([jnp.asarray(f) for f, _ in client_datasets])
            labels = jnp.stack([jnp.asarray(y) for _, y in client_datasets])
            return self.run_sharded(key, feats, labels)
        if self.ingest is not None:
            return self._run_streaming(key, client_datasets)
        return self.topology.run(key, self, client_datasets)
