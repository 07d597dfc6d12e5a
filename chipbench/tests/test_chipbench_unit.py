"""The benchmark's yardstick on the CPU: trace reduction, operation counts,
peaks, the traffic generator, the registry and the refusal without a TPU."""
from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

import cb_tiny
from yardstick import counts as CT
from yardstick import harness as HN
from yardstick import peaks as PK
from yardstick import tracing as TR
from yardstick import traffic as TF
from yardstick import weights as W

DATA = pathlib.Path(__file__).resolve().parent / "data"
BENCH = json.loads((cb_tiny.REPO / "BENCHMARK.json").read_text())


# ---------------------------------------------------------------------------
# trace → metrics
# ---------------------------------------------------------------------------

def toy_trace() -> TR.Trace:
    """Window 0–100 ns; [20,30] nests in [10,40], [60,70] alone, [95,120]
    runs past the window's end."""
    ops = {"/device:TPU:0": [("fusion.1", 10, 40), ("estep_kernel", 20, 30),
                             ("fusion.1", 60, 70), ("copy", 95, 120)]}
    spans = [("cb.window", 0, 100), ("cb.step", 0, 50),
             ("cb.submit", 45, 62), ("cb.step", 62, 100)]
    return TR.Trace(ops, spans)


def test_busy_union_clips_to_the_window():
    tr = toy_trace()
    assert TR.union(TR.clip(tr.ops["/device:TPU:0"], 0, 100)) == [
        (10, 40), (60, 70), (95, 100)]
    assert TR.busy_s(tr) == pytest.approx(45e-9)
    assert TR.window_s(tr) == pytest.approx(100e-9)
    assert TR.idle_share(tr) == pytest.approx(0.55)


def test_kernel_time_by_name_and_top_ops():
    tr = toy_trace()
    assert TR.kernel_s(tr, "estep") == pytest.approx(10e-9)
    assert TR.kernel_s(tr, "absent") is None
    top = TR.top_ops(tr)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(30e-9)
    assert [n for n, _ in top] == ["fusion.1", "estep_kernel", "copy"]


def test_a_loop_is_charged_its_own_time_not_its_body():
    ops = {"/device:TPU:0": [("%while.1", 0, 100), ("%fusion.2", 10, 40),
                             ("%fusion.3", 50, 60), ("%inner.4", 12, 20)]}
    tr = TR.Trace(ops, [("cb.window", 0, 100)])
    top = dict(TR.top_ops(tr))
    assert top["%while.1"] == pytest.approx(60e-9)
    assert top["%fusion.2"] == pytest.approx(22e-9)
    assert sum(top.values()) == pytest.approx(TR.busy_s(tr))


def test_op_name_is_the_instruction_not_its_operands():
    text = ("%log_reduce_fusion.3 = (f32[10,10]) fusion(%estep_fused.7), "
            "kind=kLoop")
    assert TR.op_name(text) == "%log_reduce_fusion.3"


def test_idle_gaps_are_named_by_the_innermost_host_span():
    gaps = TR.idle_gaps(toy_trace())
    # gaps: [0,10] in step, [40,60] mid 50 in submit (shorter than step),
    # [70,95] mid 82.5 in the second step
    assert [(n, pytest.approx(s)) for n, s in gaps] == [
        ("step", 25e-9), ("submit", 20e-9), ("step", 10e-9)]


def test_no_device_ops_reads_nothing():
    tr = TR.Trace({}, [("cb.window", 0, 10)])
    assert TR.busy_s(tr) is None and TR.idle_share(tr) is None
    assert TR.idle_gaps(tr) == [] and TR.top_ops(tr) == []


def test_recorded_trace_reduces():
    """A trace recorded on one v5e chip (a few steps of the extraction
    cell, trimmed): every reduction reads it, and shares stay in [0, 1]."""
    tr = TR.Trace.from_json((DATA / "recorded_trace.json").read_text())
    busy, win = TR.busy_s(tr), TR.window_s(tr)
    assert 0 < busy <= win
    assert 0 <= TR.idle_share(tr) < 1
    assert sum(s for _, s in TR.top_ops(tr, 10 ** 6)) == pytest.approx(
        busy, rel=1e-6)
    assert all(isinstance(n, str) for n, _ in TR.idle_gaps(tr))


def test_trace_json_round_trip():
    tr = toy_trace()
    back = TR.Trace.from_json(tr.to_json())
    assert back.ops == tr.ops and back.spans == tr.spans


# ---------------------------------------------------------------------------
# counts and peaks
# ---------------------------------------------------------------------------

def test_decoder_flops_from_shapes():
    s = {"L": 2, "d": 4, "h": 2, "hk": 1, "dh": 2, "ff": 8, "V": 10}
    per_layer = 4 * (2 + 2) * 2 + 2 * 2 * 4 + 3 * 4 * 8
    assert CT.decoder_layer_params(s) == per_layer
    # 3 tokens: 2·params·3 dense, 2·2·h·dh·(3·4/2) attention, per layer
    want = 2 * (2.0 * per_layer * 3 + 4.0 * 2 * 2 * 6)
    assert CT.decoder_row_flops(s, 3) == want
    assert CT.decoder_flops(s, [3, 3]) == 2 * want


def test_granite_flops_per_token():
    model = json.loads((cb_tiny.BENCH / "configs/granite-3-2b.json")
                       .read_text())
    s = W.dims(model)
    # 40 layers × 60.8 M matrix parameters: 4.87 GFLOP per token before
    # attention
    assert 2 * s["L"] * CT.decoder_layer_params(s) == pytest.approx(
        4.865e9, rel=1e-3)


def test_estep_counts():
    assert CT.estep_flops(500, 2048, 10, 10) == 4 * 500 * 2048 * 10 * 10
    assert CT.estep_bytes(1, 1, 1, 1) == 4 * (1 + 2 + 1 + 1 + 1)
    one = CT.em_fit_flops([50], 8, 2, iters=3, kmeans_iters=1)
    per = 4.0 * 50 * 8 * 2
    assert one == 3 * 2 * per + per + per
    assert CT.em_fit_flops([50, 0, 50], 8, 2, 3, 1) == 2 * one


def test_peak_table():
    assert PK.peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert PK.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        PK.peak("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

def test_design_is_the_stratified_lognormal():
    spec = json.loads((cb_tiny.BENCH / "traffic/extract_closed.json")
                      .read_text())["lengths"]
    g = TF.design_groups(spec, 16)
    assert g.shape == (64, 16)
    assert g.min() >= 8 and g.max() <= 512
    assert np.median(g) == pytest.approx(64, abs=1)
    assert TF.group_buckets(g, 8, 512) == [256, 512]


def test_streams_are_deterministic_in_the_seed():
    spec = cb_tiny.tiny_extract_mix()["lengths"]
    groups = TF.design_groups(spec, 4)

    def first(seed, n=40):
        rng = np.random.default_rng([seed, 1])
        src, stream = TF.PromptSource(rng, 97), TF.GroupStream(groups, rng,
                                                               4)
        out = []
        for _ in range(n):
            for length in next(stream):
                out.append(src.prompt(int(length))[1].tolist())
        return out

    big = 2 ** 33 + 3
    assert first(big) == first(big)
    assert first(big) != first(big + 1)
    # every seed sends the same multiset of lengths per cycle
    cyc = len(groups)
    a = sorted(len(p) for p in first(1, cyc))
    b = sorted(len(p) for p in first(2, cyc))
    assert a == b == sorted(groups.ravel().tolist())


def test_every_block_holds_the_same_mix_of_steps():
    spec = json.loads((cb_tiny.BENCH / "traffic/extract_closed.json")
                      .read_text())
    groups = TF.design_groups(spec["lengths"], spec["n_slots"])
    stream = TF.GroupStream(groups, np.random.default_rng(2 ** 33),
                            spec["block"])
    buckets = [TF.pow2_at_least(next(stream).max(), 8, 512)
               for _ in range(3 * len(groups))]
    per_block = {tuple(sorted(buckets[i:i + spec["block"]]))
                 for i in range(0, len(buckets), spec["block"])}
    assert per_block == {(256, 512, 512, 512)}
    tokens = [sum(int(groups[g].sum()) for g in b) for b in stream.blocks]
    assert max(tokens) / min(tokens) < 1.25


def test_root_key_takes_seeds_past_32_bits():
    import jax
    a = jax.random.key_data(W.root_key(2 ** 33 + 1))
    b = jax.random.key_data(W.root_key(1))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# registry and contract
# ---------------------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = HN.resolve(cell, cb_tiny.REPO)
    assert HN.driver(c).setup and HN.driver(c).check
    for m in c.per_layer:
        assert callable(HN.reader(c, m["name"]))
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    assert c.limits and all(isinstance(v, (int, float))
                            for v in c.limits.values())


def test_benchmark_json_keeps_the_contract():
    assert BENCH["command"][1] == "chipbench/run.py"
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = {w["name"] for w in BENCH["workloads"]}
    for entry in BENCH["configs"] + BENCH["workloads"] + \
            BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
        for key in ("why", "layer", "source"):
            text = entry.get(key, "x")
            assert 1 <= len(text) <= 200 and "\n" not in text \
                and "\t" not in text, (entry["name"], key)
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        assert (cb_tiny.BENCH / "metrics" / f"{m['name']}.py").is_file()
    assert all(w["chips"] == 1 for w in BENCH["workloads"])
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_run_py_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(cb_tiny.BENCH / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=cb_tiny.REPO, timeout=120)
    assert p.returncode == 3, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "not 'tpu'" in p.stderr


def test_run_py_refuses_a_bare_directory(tmp_path):
    """Without the program beside it, a run fails and prints nothing."""
    import shutil
    shutil.copytree(cb_tiny.BENCH, tmp_path / "chipbench")
    shutil.copy(cb_tiny.REPO / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=tmp_path, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_compile_cache_is_inside_the_checkout(monkeypatch, tmp_path):
    import jax
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    keep = {n: getattr(jax.config, n) for n in names}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        assert HN.compile_cache(tmp_path) == str(tmp_path / ".jax_cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
        assert HN.compile_cache(tmp_path) == str(tmp_path / "x")
    finally:
        for n, v in keep.items():
            jax.config.update(n, v)


# ---------------------------------------------------------------------------
# the round's server-side references
# ---------------------------------------------------------------------------

def _slots():
    rng = np.random.default_rng(0)
    return {"ids": np.array([1, 4, 7], np.int64),
            "counts": np.array([5, 3, 8], np.int64),
            "pi": rng.random((3, 2), dtype=np.float32),
            "mu": rng.random((3, 2, 4), dtype=np.float32),
            "var": rng.random((3, 2, 4), dtype=np.float32)}


def test_rows_off_counts_each_row_that_differs():
    from yardstick import fedref as FR
    want = _slots()
    pad = {k: np.concatenate([np.full((2,) + v.shape[1:], -1 if k == "ids"
                                      else 0, v.dtype), v])
           for k, v in want.items()}
    assert FR.rows_off(pad, want) == 0
    assert FR.rows_off(None, want) == 3
    bent = {k: v.copy() for k, v in pad.items()}
    bent["mu"][3, 1, 2] = np.nextafter(bent["mu"][3, 1, 2], 2.0)
    bent["counts"][4] += 1
    assert FR.rows_off(bent, want) == 2
    half = {k: v[:3] for k, v in pad.items()}         # two pads, slot 1
    assert FR.rows_off(half, want) == 2


def test_loss_gap_reads_a_missing_step_as_a_zero_loss():
    from yardstick import fedref as FR
    ref = np.array([2.0, 1.0, 1.0, 0.0])
    assert FR.loss_gap(ref, ref) == 0.0
    assert FR.loss_gap(ref[:2], ref) == pytest.approx(1.0 / 1.0)
    assert FR.loss_gap(ref + 0.01, ref) == pytest.approx(0.01)


def test_program_config_runs_the_rotary_base_as_run():
    """Granite's file holds the published rope θ; the program runs its
    registered one, which the file states under ``as_run``."""
    import json as _json
    from yardstick.program import program_config
    model = _json.loads((cb_tiny.BENCH / "configs/granite-3-2b.json")
                        .read_text())
    cfg = program_config(model)
    assert model["rope_theta"] == 10000.0
    assert cfg.rope_theta == model["as_run"]["rope_theta"] == 1e6
    assert cfg.n_layers == model["num_hidden_layers"] == 40
    with pytest.raises(ValueError):
        program_config(dict(model, hidden_size=1024))
