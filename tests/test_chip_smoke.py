"""``chip_smoke.py`` rehearsed on the CPU, and its refusals.

The chip run itself needs a TPU; here the same service round runs at a
cut-down size (2 layers, d_model 128) so its phases, checks and reporting
stay working, and the script is shown to exit non-zero without a result
line where there is no TPU or no repository next to it.  The compile-cache
helper's placement is checked too.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax

from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _load_script():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod     # dataclasses look it up
    spec.loader.exec_module(mod)
    return mod


def test_service_round_rehearsal():
    """Every phase and check of the one-chip round passes at a tiny size:
    extract → fit → broker → close_round → infer, checks (a)–(c)."""
    cs = _load_script()
    report = cs.Report()
    svc = cs.service_round(report, cs.Size(
        n_layers=2, d_model=128, n_prompts=8, min_len=4, max_len=32,
        n_slots=8, probe_len=11, n_infer=8))
    assert report.failed == []
    assert svc.rounds == 1 and svc.head is not None


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    out = _run(ROOT, SCRIPT)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "not a TPU" in out.stderr


def test_refuses_without_the_repository(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    out = _run(tmp_path, alone)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_compile_cache_placement(monkeypatch):
    """Without JAX_COMPILATION_CACHE_DIR the cache sits at the fixed
    ``<checkout>/.jax_cache``; with it, JAX's own setting stands."""
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        assert compile_cache.enable() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", was)
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
        assert compile_cache.enable() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_gitignore_lists_caches():
    """The compile cache, chip outputs and test caches stay out of git."""
    ignored = (ROOT / ".gitignore").read_text().split()
    for entry in (".jax_cache/", "chiprun_out/", ".hypothesis/",
                  ".pytest_cache/", "__pycache__/"):
        assert entry in ignored
