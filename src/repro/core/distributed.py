"""Distributed FedPFT round — the paper's one-shot transfer as mesh
collectives (DESIGN.md §5).

``shard_map`` over the "data" axis: each shard owns I/shards clients, runs
feature-space EM locally (ONE batched fit over the clients × classes
stack — a single fused E-step program per EM iteration, DESIGN.md §8,
with per-shard-offset PRNG seeds so no two clients share a key), packs
the bf16 wire pytree, and ``all_gather``s it — the all_gather IS the one-shot
communication round, so the dry-run HLO shows exactly Eqs. 9-11 worth of
bytes on the wire (vs an all_gather of raw features for the Centralized
baseline). The server side (sampling + head training) then runs
data-parallel on the gathered, replicated parameters.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import gmm as G


def validate_cohort(I: int, n_shards: int, *, where: str = "fedpft_transfer"
                    ) -> None:
    """Reject cohorts that don't shard evenly BEFORE shard_map is built.

    Without this, an uneven cohort dies deep inside shard_map with a bare
    "sharded dimension not divisible" shape error that names neither the
    cohort nor the mesh.
    """
    if n_shards < 1:
        raise ValueError(f"{where}: mesh 'data' axis must have >= 1 shard, "
                         f"got {n_shards}")
    if I % n_shards != 0:
        valid = [n for n in range(1, I + 1) if I % n == 0]
        raise ValueError(
            f"{where}: cohort of I={I} clients does not shard evenly over "
            f"the {n_shards}-way 'data' mesh axis (I % n_shards == "
            f"{I % n_shards}). Each shard must own the same number of "
            f"clients — pad the cohort with empty clients to a multiple of "
            f"{n_shards}, or use a shard count that divides {I} "
            f"(one of {valid}).")


def data_axis_size(mesh, *, where: str = "fedpft_transfer") -> int:
    """The mesh's client-sharding degree — with an actionable error when
    the mesh has no "data" axis (shared by ``fl.api.FedSession``)."""
    if "data" not in mesh.axis_names:
        raise ValueError(
            f"{where}: mesh has axes {tuple(mesh.axis_names)} but "
            "the one-shot transfer shards clients over a 'data' axis — "
            "build the mesh with launch.mesh.make_sim_mesh(n) (simulated "
            "lane) or make_production_mesh()")
    return mesh.shape["data"]


def client_seeds(shard, I_local: int, seed: int) -> jax.Array:
    """Globally-unique per-client PRNG seeds for one shard.

    shard i owns clients [i·I_local, (i+1)·I_local) — disjoint across the
    "data" axis, and equal to the host-level ``PRNGKey(j + seed)`` layout
    when there is a single shard.
    """
    return (jnp.arange(I_local, dtype=jnp.uint32)
            + jnp.uint32(shard) * jnp.uint32(I_local) + jnp.uint32(seed))


def fedpft_transfer(mesh, feats: jax.Array, labels: jax.Array,
                    n_classes: int, cfg: G.GMMConfig, seed: int = 0):
    """One-shot FedPFT round over a client-sharded dataset.

    feats: (I, N, d) — I clients (sharded over "data"), N padded samples.
    labels: (I, N) with −1 padding.

    Returns (wire pytree stacked (I, C, K, …) REPLICATED on every shard,
    counts (I, C), logliks (I, C)) — i.e. post-transfer server state.  The
    wire pytree is ``gmm.pack_wire``'s bf16 layout — the SAME field set /
    tril packing the host codec (``fl.api``) serializes, so
    ``fl.api.messages_from_wire`` can account it byte-for-byte.  The
    per-class EM log-likelihoods ride along (O(I·C) scalars next to the
    O(I·C·K·d²) wire — the Theorem 6.1 bound evaluator needs them).
    """
    I = feats.shape[0]
    validate_cohort(I, data_axis_size(mesh))
    if labels.shape[0] != I:
        raise ValueError(
            f"fedpft_transfer: feats carries I={I} clients but labels "
            f"carries {labels.shape[0]} — both lead with the client axis")

    def local(f, y):
        # f: (I_local, N, d); y: (I_local, N)
        I_local = f.shape[0]
        shard = jax.lax.axis_index("data").astype(jnp.uint32)
        # offset by the shard's global client base — without it client j on
        # every shard fit with the identical PRNGKey(j + seed)
        keys = jax.vmap(jax.random.PRNGKey)(
            client_seeds(shard, I_local, seed))

        # the whole (I_local × C) stack of EM fits is one batched program
        # (a single pallas_call per EM iteration on TPU — DESIGN.md §8)
        gmms, counts, lls = G.fit_classwise_gmms_batched(keys, f, y,
                                                         n_classes, cfg)
        packed = G.pack_wire(gmms, cfg.cov_type)
        # ---- the one-shot transfer: GMM parameters cross the mesh ----
        gathered = jax.tree.map(
            lambda a: jax.lax.all_gather(a, "data", axis=0, tiled=True),
            packed)
        counts_g = jax.lax.all_gather(counts, "data", axis=0, tiled=True)
        lls_g = jax.lax.all_gather(lls, "data", axis=0, tiled=True)
        return gathered, counts_g, lls_g

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P("data"), P("data")),
                         out_specs=(P(), P(), P()),
                         check_vma=False)(feats, labels)


def raw_feature_transfer(mesh, feats: jax.Array, labels: jax.Array):
    """Centralized baseline: every client's raw features cross the mesh."""
    def local(f, y):
        f16 = f.astype(jnp.bfloat16)     # paper's 16-bit wire encoding
        return (jax.lax.all_gather(f16, "data", axis=0, tiled=True),
                jax.lax.all_gather(y, "data", axis=0, tiled=True))
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P("data"), P("data")),
                         out_specs=(P(), P()), check_vma=False)(feats, labels)


def expected_wire_bytes(cov_type: str, d: int, K: int, C: int,
                        n_clients: int) -> int:
    """What Eqs. 9-11 predict the all_gather above moves per shard."""
    return G.comm_bytes(cov_type, d, K, C, 2) * n_clients
