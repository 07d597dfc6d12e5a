"""Helpers of the benchmark's CPU tests: a copy of the benchmark in a
temporary checkout, with tiny cells added the way a later change adds a
cell — new files and new entries only."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TINY_MODEL = {"name": "tiny", "arch": None, "hidden_size": 64,
              "intermediate_size": 128, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "num_hidden_layers": 2, "vocab_size": 97,
              "torch_dtype": "bfloat16", "rope_theta": 10000.0,
              "as_run": {"rope_theta": 10000.0, "rms_norm_eps": 1e-6}}


def tiny_extract_mix():
    mix = json.loads((BENCH / "traffic" / "extract_closed.json").read_text())
    mix.update(n_slots=4, max_seq=64, queue_depth=8, check_rows=6)
    mix["lengths"].update(median=12, min=2, max=64, design_size=64)
    return mix


def tiny_round_mix():
    mix = json.loads((BENCH / "traffic" / "round_silo.json").read_text())
    mix.update(n_clients=3, n_samples=96, n_classes=3, n_components=3,
               held_out=300)
    mix["head"]["n_steps"] = 200
    return mix


def like(bench: dict, name: str, template: str) -> None:
    """List cell ``name`` in every metric that lists ``template``."""
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and template in m["workloads"]:
            m["workloads"].append(name)


def checkout(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout in ``tmp`` holding the benchmark plus two tiny cells:
    ``tiny.extract`` (like granite-3-2b.extract_closed) and
    ``tiny.round`` (like granite-3-2b.round_silo)."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "chipbench/configs/tiny.json").write_text(json.dumps(TINY_MODEL))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "CPU rehearsal"})
    for name, mix, template in (
            ("tiny.extract", tiny_extract_mix(),
             "granite-3-2b.extract_closed"),
            ("tiny.round", tiny_round_mix(), "granite-3-2b.round_silo")):
        traffic = "tiny_" + name.split(".")[1]
        (root / f"chipbench/traffic/{traffic}.json").write_text(
            json.dumps(mix))
        bench["workloads"].append({"name": name, "config": "tiny",
                                   "traffic": traffic, "chips": 1,
                                   "why": "CPU rehearsal"})
        like(bench, name, template)
        shutil.copy(root / f"chipbench/limits/{template}.json",
                    root / f"chipbench/limits/{name}.json")
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def run(root: pathlib.Path, cell: str, seed: int = 2 ** 33 + 17,
        seconds: float = 1.0, trace: bool = False) -> dict:
    """One run of ``cell`` on the CPU, JAX's compile cache left as the
    test process has it."""
    import time
    from yardstick import harness
    c = harness.resolve(cell, root)
    keep = harness.compile_cache
    harness.compile_cache = lambda _root: "unchanged"
    try:
        return harness.run(c, seed, seconds, trace, time.perf_counter(),
                           platform="cpu", log=lambda msg: None)
    finally:
        harness.compile_cache = keep
