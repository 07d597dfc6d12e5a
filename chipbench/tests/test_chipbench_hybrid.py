"""CPU rehearsal of the hybrid extraction cell at a tiny size: a whole run
is correct; its weights are the reference's layer by layer; a fault in the
timed path and the fp8 control make it incorrect; the work is counted as
by hand; and the configuration file is the registered architecture."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cb_tiny
from yardstick import hybrid as HY
from yardstick import weights as W

SEED = 2 ** 33 + 17
FILE = json.loads((cb_tiny.BENCH / "configs" /
                   "granite-4-h-micro.json").read_text())
# one period of the published layer kinds at tiny widths: H·P = 2·d
TINY = dict(FILE, name="tinyh", arch=None, hidden_size=64,
            intermediate_size=128, shared_intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=97, mamba_n_heads=8, mamba_d_head=16,
            mamba_d_state=8, mamba_chunk_size=16, num_hidden_layers=10,
            layer_types=FILE["layer_types"][:10])
CELL = "tinyh.extract"
TEMPLATE = "granite-4-h-micro.extract_closed"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = cb_tiny.checkout(tmp_path_factory.mktemp("hybrid"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (root / "chipbench/configs/tinyh.json").write_text(json.dumps(TINY))
    mix = cb_tiny.tiny_extract_mix()
    mix["kind"] = "extract_hybrid"
    (root / "chipbench/traffic/tinyh_extract.json").write_text(
        json.dumps(mix))
    bench["configs"].append({"name": "tinyh", "source": "test",
                             "file": "chipbench/configs/tinyh.json",
                             "reduced": [], "why": "CPU rehearsal"})
    bench["workloads"].append({"name": CELL, "config": "tinyh",
                               "traffic": "tinyh_extract", "chips": 1,
                               "why": "CPU rehearsal"})
    cb_tiny.like(bench, CELL, TEMPLATE)
    (root / f"chipbench/limits/{CELL}.json").write_text(
        (root / f"chipbench/limits/{TEMPLATE}.json").read_text())
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def test_tiny_hybrid_run_is_correct(root):
    res = cb_tiny.run(root, CELL)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"extract_tokens_per_s", "setup_s"}


def test_traced_hybrid_run_reports_host_counts_only(root):
    res = cb_tiny.run(root, CELL, trace=True)
    assert res["correct"] is True
    # no device plane and no peaks off the chip: device_idle, admit_ms
    # and hybrid_mfu read nothing
    assert set(res["metrics"]) == {"pad_share.closed"}


def test_weights_match_the_reference_layer_by_layer():
    s = HY.dims(TINY)
    p = HY.make_params(5, TINY)
    key = W.root_key(5)
    mix_ids, attn_ids = HY.layer_ids(s)
    assert list(attn_ids) == [5]
    dtb = HY.dt_bias(5, s)
    for i, layer in enumerate(mix_ids):
        ref = HY.served(HY.mixer_layer_f32(
            W.layer_key(key, jnp.int32(layer)), s, jnp.asarray(dtb[i])),
            jnp.bfloat16)
        got = jax.tree.map(lambda a: a[i], p["blocks"]["mamba"])
        jax.tree.map(lambda g, r: np.testing.assert_array_equal(
            np.asarray(g, np.float32), np.asarray(r)), got, ref)
    for i, layer in enumerate(attn_ids):
        ref = W.layer_f32(W.layer_key(key, jnp.int32(layer)), s)
        for k, v in ref.items():
            np.testing.assert_array_equal(
                np.asarray(p["blocks"]["attn"][k][i], np.float32),
                np.asarray(W.served(v, jnp.bfloat16)))


@pytest.mark.parametrize("fault", ["norm_before_gate", "fp8_control"])
def test_a_fault_and_the_control_are_not_correct(root, monkeypatch, fault):
    from repro import serve
    from repro.models import mamba2 as MM
    if fault == "norm_before_gate":
        def gated_norm(y, z, gn, eps):
            return (MM.rms_norm(y.astype(jnp.float32), gn, eps)
                    * jax.nn.silu(z.astype(jnp.float32))).astype(y.dtype)
        monkeypatch.setattr(MM, "gated_norm", gated_norm)
    else:
        def factory(cfg):
            def feats(params, tokens, length):
                return HY.features(SEED, TINY, tokens,
                                   jnp.maximum(length, 1), quant="fp8")
            return feats
        monkeypatch.setattr(serve, "make_feature_step", factory)
    res = cb_tiny.run(root, CELL, seed=SEED)
    assert res["correct"] is False, (fault, res["checks"])


def test_count_is_the_hand_count():
    """d 64, ff 128, 4/2 heads × 16, H 8 × P 16, N 8, K 4; 9 mixers and
    one attention layer; a row of 5 tokens."""
    mixer = 64 * (2 * 128 + 2 * 8 + 8) + 128 * 64      # in_ and out_proj
    mlp = 3 * 64 * 128
    attn = 64 * (4 + 2 + 2) * 16 + 4 * 16 * 64
    per_mix = 2 * (mixer + mlp) + 4 * 8 * 8 * 16 + 2 * 4 * (128 + 16)
    want = 9 * per_mix * 5 + (2 * (attn + mlp) * 5 + 4 * 4 * 16 * 15)
    assert HY.row_flops(HY.dims(TINY), 5) == want
    assert HY.flops(TINY, [5, 5]) == 2 * want


def test_the_file_is_the_registered_architecture():
    from repro.configs import get_config
    cfg = HY.program_config(FILE)
    assert cfg == get_config("granite-4-h-micro")
    assert FILE["reduced"] == [] and HY.dims(FILE)["kinds"] == "mmmmmammmm"
    with pytest.raises(ValueError, match="differ"):
        HY.program_config(dict(FILE, residual_multiplier=1.0))
