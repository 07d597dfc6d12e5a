"""CPU rehearsal of the extraction cells at a tiny size: a whole run is
correct; the control and each fault of the timed path make it incorrect;
and a later change adds a cell and a metric with new files alone."""
from __future__ import annotations

import filecmp
import json

import jax.numpy as jnp
import numpy as np
import pytest

import cb_tiny
from yardstick import reference as REF


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return cb_tiny.checkout(tmp_path_factory.mktemp("extract"))


def test_tiny_extract_run_is_correct(root):
    res = cb_tiny.run(root, "tiny.extract")
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"extract_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


def test_traced_run_reports_host_counts_not_device_numbers(root):
    res = cb_tiny.run(root, "tiny.extract", trace=True)
    assert res["correct"] is True
    # a CPU trace holds no device plane: device metrics are left out
    assert set(res["metrics"]) == {"pad_share.closed"}
    assert 0 < res["metrics"]["pad_share.closed"]["value"] < 100
    assert res["device"]["busy_s"] == 0.0
    assert res["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_weights_match_the_reference_layer_by_layer():
    """The stacked weights the program gets are the reference's per-layer
    draws, cast to the served type."""
    from yardstick import weights as W
    model = cb_tiny.TINY_MODEL
    s = W.dims(model)
    p = W.make_params(5, model)
    key = W.root_key(5)
    for layer in range(s["L"]):
        ref = W.layer_f32(W.layer_key(key, jnp.int32(layer)), s)
        for k, v in ref.items():
            np.testing.assert_array_equal(
                np.asarray(p["blocks"][k][layer], np.float32),
                np.asarray(W.served(v, jnp.bfloat16)))


def broken_step(fault):
    """A feature step with one fault planted in it."""
    from repro import serve

    real = serve.make_feature_step

    def factory(cfg):
        step = real(cfg)

        def feats(params, tokens, length):
            if fault == "mask_off_by_one":
                return step(params, tokens, jnp.maximum(length - 1, 1))
            if fault == "token_altered":
                tokens = tokens.at[:, 0].set((tokens[:, 0] + 1)
                                             % cfg.vocab_size)
                return step(params, tokens, length)
            out = step(params, tokens, length)
            if fault == "half_batch":
                half = out.shape[0] // 2
                return out.at[half:].set(0.0)
            raise ValueError(fault)
        return feats
    return factory


@pytest.mark.parametrize("fault", ["mask_off_by_one", "token_altered",
                                   "half_batch", "state_unchanged"])
def test_a_fault_in_the_timed_path_is_not_correct(root, monkeypatch, fault):
    from repro import serve
    from repro.models import model as M
    if fault == "state_unchanged":
        # the layer stack hands its residual state back untouched
        monkeypatch.setattr(M, "_run_transformer",
                            lambda cfg, x, blocks, cache, **kw:
                            (x, cache, jnp.zeros(())))
    else:
        monkeypatch.setattr(serve, "make_feature_step", broken_step(fault))
    res = cb_tiny.run(root, "tiny.extract")
    assert res["correct"] is False, (fault, res["checks"])


def test_the_fp8_control_is_not_correct(root, monkeypatch):
    """The reference at fp8 in the program's place fails the limit."""
    from repro import serve
    model = cb_tiny.TINY_MODEL
    seed = 2 ** 33 + 17

    def factory(cfg):
        def feats(params, tokens, length):
            return REF.features(seed, model, tokens,
                                jnp.maximum(length, 1).astype(jnp.float32),
                                quant="fp8")
        return feats
    monkeypatch.setattr(serve, "make_feature_step", factory)
    res = cb_tiny.run(root, "tiny.extract", seed=seed)
    assert res["correct"] is False, res["checks"]


def test_a_cell_and_a_metric_are_added_by_files_alone(root, tmp_path):
    """A dummy cell (new configuration, mix, limits) and a dummy per-layer
    metric, registered in a temporary checkout without editing any file
    the benchmark has."""
    import shutil
    other = tmp_path / "later"
    shutil.copytree(root, other)
    before = {p.relative_to(other): p.read_bytes()
              for p in (other / "chipbench").rglob("*") if p.is_file()}
    bench = json.loads((other / "BENCHMARK.json").read_text())
    model = dict(cb_tiny.TINY_MODEL, name="dummy", num_hidden_layers=1)
    (other / "chipbench/configs/dummy.json").write_text(json.dumps(model))
    mix = cb_tiny.tiny_extract_mix()
    mix["lengths"]["median"] = 20
    (other / "chipbench/traffic/dummy_mix.json").write_text(json.dumps(mix))
    (other / "chipbench/limits/dummy.dummy_mix.json").write_text(
        json.dumps({"feature_rel_err": 0.05}))
    (other / "chipbench/metrics/dummy_rows.closed.py").write_text(
        "def read(run):\n    return float(len(run.record['lengths']))\n")
    bench["configs"].append({"name": "dummy", "source": "test",
                             "file": "chipbench/configs/dummy.json",
                             "reduced": [], "why": "dummy"})
    bench["workloads"].append({"name": "dummy.dummy_mix", "config": "dummy",
                               "traffic": "dummy_mix", "chips": 1,
                               "why": "dummy"})
    cb_tiny.like(bench, "dummy.dummy_mix", "tiny.extract")
    bench["per_layer"].append({"name": "dummy_rows.closed", "unit": "rows",
                               "better": "higher", "source": "host_clock",
                               "layer": "admission", "moves":
                               "extract_tokens_per_s",
                               "workloads": ["dummy.dummy_mix"]})
    (other / "BENCHMARK.json").write_text(json.dumps(bench))
    res = cb_tiny.run(other, "dummy.dummy_mix", trace=True)
    assert res["correct"] is True
    assert res["metrics"]["dummy_rows.closed"]["value"] > 0
    for rel, data in before.items():
        assert (other / rel).read_bytes() == data, rel
    assert filecmp.cmp(cb_tiny.BENCH / "run.py", other / "chipbench/run.py")
