"""One run of one cell: resolve it by name, set up, measure, check, report.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

    chipbench/configs/<config>.json   sizes as run (the cell's ``file``)
    chipbench/traffic/<traffic>.json  the mix's parameters; its ``kind``
                                      names the driver
    chipbench/drivers/<kind>.py       set-up, window and comparison
    chipbench/metrics/<metric>.py     ``read(run)`` → number or None
    chipbench/limits/<workload>.json  each compared number's limit

So a cell, a mix or a metric is added by adding files and entries.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

from yardstick import peaks as PK
from yardstick import tracing as TR

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: str
    traffic: str
    model: Dict[str, Any]
    mix: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: pathlib.Path


@dataclasses.dataclass
class Check:
    """One compared number beside its limit (it passes at or under it)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)    # NaN fails


@dataclasses.dataclass
class Window:
    """What a driver's measured window hands back."""
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    record: Dict[str, Any]        # what per-layer readers read


@dataclasses.dataclass
class Run:
    """Everything a per-layer reader may read."""
    cell: Cell
    trace: Optional[TR.Trace]
    record: Dict[str, Any]
    peaks: Dict[str, float]


def _read_json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


def _applies(entry: Dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def resolve(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    model = _read_json(root / configs[w["config"]]["file"])
    bench_dir = root / "chipbench"
    mix = _read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = _read_json(bench_dir / "limits" / f"{name}.json")
    return Cell(name=name, chips=int(w["chips"]), config=w["config"],
                traffic=w["traffic"], model=model, mix=mix, limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                root=root)


def _load_file(path: pathlib.Path, tag: str):
    if not path.is_file():
        raise FileNotFoundError(f"{tag}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{tag}_{path.stem}".replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(cell: Cell):
    return _load_file(cell.root / "chipbench" / "drivers"
                      / f"{cell.mix['kind']}.py", "driver")


def reader(cell: Cell, metric: str) -> Callable[[Run], Optional[float]]:
    return _load_file(cell.root / "chipbench" / "metrics" / f"{metric}.py",
                      "metric").read


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------


def chips(want: int, platform: str = "tpu"):
    """The devices of this run; ``NoChip`` where JAX has fewer ``want``
    devices of ``platform`` (it never falls back to another)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"JAX's devices are {devs[0].platform!r} "
                     f"({devs[0].device_kind}), not {platform!r}")
    if len(devs) < want:
        raise NoChip(f"{len(devs)} {platform} device(s); the cell needs "
                     f"{want}")
    return devs


def compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else the fixed ``<checkout>/.jax_cache``; every program is kept."""
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts backend compiles and persistent-cache hits and misses, and
    keeps the end time, duration and kind of every trace, lowering and
    compile."""

    KINDS = {"jaxpr_trace_duration": "trace",
             "jaxpr_to_mlir_module_duration": "lower",
             "backend_compile_duration": "compile"}

    def __init__(self):
        import jax
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self.events: List = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        kind = self.KINDS.get(event.rsplit("/", 1)[-1])
        if kind:
            self.events.append((time.perf_counter(), secs, kind))
        if kind == "compile":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class GcWatch:
    """Counts the interpreter's garbage collections and their time by
    generation, while it is entered, and keeps the end time, duration and
    generation of each."""

    def __init__(self):
        self.runs = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self.events: List = []
        self._t0 = 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            g = info["generation"]
            t = time.perf_counter()
            self.runs[g] += 1
            self.seconds[g] += t - self._t0
            self.events.append((t, t - self._t0, f"gc{g}"))

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)
        return False


class StepLog:
    """Host readings of each step of a window: its span on the host clock
    and the process's CPU seconds in it.  For the log: what a long step
    spent its time on."""

    def __init__(self):
        self.rows: List = []

    @contextlib.contextmanager
    def step(self):
        c0, t0 = time.process_time(), time.perf_counter()
        yield
        self.rows.append((t0, time.perf_counter(),
                          time.process_time() - c0))

    def durations(self) -> List[float]:
        return [t1 - t0 for t0, t1, _ in self.rows]

    def longest(self, events, n: int = 3) -> List[str]:
        """The ``n`` longest steps, each with the events (end time,
        duration, kind) that ended inside it."""
        med = float(sorted(self.durations())[len(self.rows) // 2]) \
            if self.rows else 0.0
        out = []
        for t0, t1, cpu in sorted(self.rows, key=lambda r: r[0] - r[1])[:n]:
            inside: Dict[str, List[float]] = {}
            for e, d, k in events:
                if t0 <= e <= t1:
                    inside.setdefault(k, []).append(d)
            kinds = ", ".join(f"{k} x{len(d)} {sum(d):.4f} s"
                              for k, d in sorted(inside.items()))
            out.append(f"{t1 - t0:.4f} s (median {med:.4f}): cpu {cpu:.4f} "
                       f"s, events [{kinds}]")
        return out


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, platform: str = "tpu",
        log=lambda msg: print(msg, file=sys.stderr, flush=True)) -> Dict:
    """Set up, measure, check; returns the result object (not printed)."""
    devs = chips(cell.chips, platform)
    kind = devs[0].device_kind
    peaks = PK.peak(kind) if platform == "tpu" else {}
    where = compile_cache(cell.root)
    counter = CompileCounter()
    drv = driver(cell)
    log(f"cell {cell.name}: {len(devs)} × {kind}; compile cache {where}")

    state = drv.setup(cell, seed, log)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: {counter.compiles} compiles, "
        f"{counter.hits} cache hits, {counter.misses} misses")

    compiles_before = counter.compiles
    tdir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    gcw = GcWatch()
    try:
        if trace:
            with TR.capture(tdir), gcw:
                win = drv.window(state, seconds)
            tr = TR.load(tdir)
        else:
            with gcw:
                win = drv.window(state, seconds)
            tr = None
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    in_window = counter.compiles - compiles_before
    mem = memory_peak(devs)
    log(f"window: {win.attempted} attempted, {win.failed} failed, "
        f"{in_window} compiles inside it; memory peak {mem} B; garbage "
        f"collections by generation {gcw.runs} taking {gcw.seconds} s; "
        f"{win.record.get('note', '')}")
    steps = win.record.get("step_log")
    if steps is not None:
        for line in steps.longest(counter.events + gcw.events):
            log(f"long step: {line}")

    checks = drv.check(state, cell, seed, log)

    if trace:
        rec = Run(cell=cell, trace=tr, record=win.record, peaks=peaks)
        metrics = {}
        for m in cell.per_layer:
            value = reader(cell, m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(win.end_to_end, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {"correct": bool(checks) and all(c.ok for c in checks),
              "attempted": win.attempted, "failed": win.failed,
              "metrics": metrics, "device": device}
    if trace:
        busy = TR.busy_s(tr)
        device["busy_s"] = busy if busy is not None else 0.0
        device["window_s"] = TR.window_s(tr)
        result["breakdown"] = {"device_ops": TR.top_ops(tr),
                               "idle_gaps": TR.idle_gaps(tr)}
    for c in checks:
        log(f"check {c.name}: {c.value!r} limit {c.limit!r} "
            f"{'pass' if c.ok else 'FAIL'}")
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result
