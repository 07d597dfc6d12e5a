"""Chip-compiler rehearsal: the main path compiles for a described TPU v5e.

Nothing here runs on a chip.  Each test lowers one program of the FedPFT
service path at real width and compiles it for chips of a described
``v5e:2x2`` topology with the TPU compiler that ships with JAX, which
refuses what the chip would refuse: misaligned Pallas blocks, VMEM
overuse, programs that do not fit the 16 GiB of HBM.  The topology is
described inside a module fixture, never at import, and every compile runs
in this test process (only one process may hold the TPU library).
"""
import dataclasses
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import serve
from repro.configs import get_config
from repro.core import distributed as DF
from repro.core import gmm as G
from repro.core import head as H
from repro.fl import round as FR
from repro.kernels.gmm_estep import estep_fused
from repro.launch import aot_cache as AC
from repro.launch import input_specs as IS
from repro.launch.hlo_cost import HloCost
from repro.models import model as M

HBM_BYTES = 16 * 2**30          # one v5e chip
D = 2048                        # granite-3-2b's d_model


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def tpu_dispatch(monkeypatch):
    """``kernels.ops`` asks JAX's default backend, which is the CPU here:
    present a TPU so the client fit takes the compiled-kernel branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB > one chip's HBM"
    return used


@pytest.mark.parametrize("C", [4, 10])
def test_estep_fused_batched_compiles(one_chip, C):
    """B = 10 clients × C classes fits in one Mosaic call, N=4096, K=10,
    d=2048: the batched case the old (1, bn) logsumexp block made the
    compiler refuse."""
    Bx, N, K = 10, 4096, 10
    B = Bx * C
    f32 = jnp.float32
    compiled = jax.jit(partial(estep_fused, interpret=False)).lower(
        _sds((Bx, N, D), f32, one_chip), _sds((B, K, D), f32, one_chip),
        _sds((B, K, D), f32, one_chip), _sds((B, K), f32, one_chip)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_client_fit_runs_the_kernel(one_chip, tpu_dispatch):
    """On a TPU the client's classwise fit lowers its E-step to the
    compiled kernel — neither the interpreter nor the reference."""
    cfg = G.GMMConfig(2, "diag")
    fit = jax.jit(G.fit_classwise_gmms, static_argnums=(3, 4))
    compiled = fit.lower(_sds((2,), jnp.uint32, one_chip),
                         _sds((32, D), jnp.float32, one_chip),
                         _sds((32,), jnp.int32, one_chip), 4, cfg).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_round_program_compiles_for_serving_grid(one_chip):
    """The one signature a service warms (slot stack, diag, d=2048)
    compiles, costs and serializes for one chip."""
    from jax.experimental import serialize_executable as SE
    sig = AC.serving_grid(64, 4, 2, D)[0]
    specs = jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, one_chip), IS.round_specs_for(sig))
    compiled = FR.round_program.lower(
        *specs, sig=sig, head_cfg=H.HeadConfig()).compile()
    cost = HloCost(compiled.as_text()).total()
    assert cost.elem_flops > 0
    assert SE.serialize(compiled)[0]
    _fits(compiled)


def test_feature_step_compiles_full_width(one_chip):
    """granite-3-2b's feature step at its published width (2 of its 40
    layers) over a 16-slot × 256-token service batch."""
    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=2)
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(partial(M.init_params, cfg), jax.random.PRNGKey(0)))
    assert params["embed"].shape == (cfg.vocab_size, D)
    compiled = jax.jit(serve.make_feature_step(cfg)).lower(
        params, _sds((16, 256), jnp.int32, one_chip),
        _sds((16,), jnp.int32, one_chip)).compile()
    _fits(compiled)


def test_packed_feature_step_compiles_full_width(one_chip):
    """The same step over a packed batch: 4 rows × 512 tokens holding up
    to 16 requests' segments, the shape most steps of the extraction
    cells take."""
    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=2)
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(partial(M.init_params, cfg), jax.random.PRNGKey(0)))
    compiled = jax.jit(serve.make_feature_step(cfg)).lower(
        params, _sds((4, 512), jnp.int32, one_chip),
        _sds((4, 16), jnp.int32, one_chip)).compile()
    _fits(compiled)


def test_hybrid_feature_step_compiles_full_width(one_chip):
    """granite-4-h-micro's feature step at its published width, one whole
    period (10 layers: 9 Mamba-2 mixers, attention at index 5) over the
    16-slot × 512-token batch its extraction cell steps: the SSD's
    (16, 64, 256, 256) decay blocks and the NoPE attention fit one chip."""
    cfg = dataclasses.replace(get_config("granite-4-h-micro"), n_layers=10,
                              remat=False)
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(partial(M.init_params, cfg), jax.random.PRNGKey(0)))
    assert params["blocks"]["mamba"]["mixer"]["w_in"].shape == (9, D, 8512)
    compiled = jax.jit(serve.make_feature_step(cfg)).lower(
        params, _sds((16, 512), jnp.int32, one_chip),
        _sds((16,), jnp.int32, one_chip)).compile()
    _fits(compiled)


def test_packed_hybrid_feature_step_compiles_full_width(one_chip):
    """The same period over a packed batch: 16 rows × 512 tokens holding up
    to 16 requests' segments, the largest call the hybrid's extraction
    cell makes, with the segment masks in the SSD and the conv."""
    cfg = dataclasses.replace(get_config("granite-4-h-micro"), n_layers=10,
                              remat=False)
    params = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(partial(M.init_params, cfg), jax.random.PRNGKey(0)))
    compiled = jax.jit(serve.make_feature_step(cfg)).lower(
        params, _sds((16, 512), jnp.int32, one_chip),
        _sds((16, 16), jnp.int32, one_chip)).compile()
    _fits(compiled)


def test_mesh_transfer_compiles_on_four_chips(topo, tpu_dispatch):
    """The mesh round's client phase over four chips: sharded EM with the
    compiled kernel, then the all-gather that is the one-shot transfer."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    shard = NamedSharding(mesh, P("data"))
    transfer = jax.jit(partial(DF.fedpft_transfer, mesh, n_classes=4,
                               cfg=G.GMMConfig(2, "diag")))
    compiled = transfer.lower(_sds((16, 256, D), jnp.float32, shard),
                              _sds((16, 256), jnp.int32, shard)).compile()
    text = compiled.as_text()
    assert "all-gather" in text and "tpu_custom_call" in text
