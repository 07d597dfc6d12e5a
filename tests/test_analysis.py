"""Tier-1: the static-analysis framework + runtime sanitizer (DESIGN.md §10).

Four bars:

1. **Regression corpus** — every ``tests/fixtures/lint/*_bad.py`` fires
   exactly its expected rule and every ``*_good.py`` twin is clean; in
   particular the three *historical* key-discipline bugs (PR 1 synthesis
   serial chain, PR 2 kmeans same-key reuse, PR 4 cross-shard seed
   collision) are all retro-detected.
2. **Self-clean gate** — the live tree (src/repro + benchmarks +
   examples) has zero unsuppressed findings, AST and semantic.
3. **Pure checkers** — the Pallas contract checks fire on synthetic
   violations and pass tiled/aligned geometry.
4. **Sanitizer** — the runtime key-reuse tracer raises on concrete
   double consumption, skips tracers, honours ``reset()``, and restores
   ``jax.random`` / ``jax.config`` state on exit.
"""
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (KeyReuseError, analyze_paths, gating, sanitize)
from repro.analysis.core import SemanticRule, SourceFile, _default_rules

ROOT = pathlib.Path(__file__).resolve().parents[1]
FIXDIR = ROOT / "tests" / "fixtures" / "lint"

# bad fixture → the exact rule set it must fire (host_sync is path-gated
# and handled separately)
EXPECT = {
    "pr1_synthesis_bad.py": {"KEY-CHAIN"},
    "pr2_kmeans_bad.py": {"KEY-REUSE"},
    "pr4_shard_seeds_bad.py": {"KEY-SHARD"},
    "key_reuse_bad.py": {"KEY-REUSE"},
    "key_chain_bad.py": {"KEY-CHAIN"},
    "inline_jit_bad.py": {"CHURN-INLINE-JIT"},
    "static_arg_bad.py": {"CHURN-STATIC"},
}


def _ast_findings(path):
    return analyze_paths([str(path)], semantic=False)


class TestFixtureCorpus:
    @pytest.mark.parametrize("name", sorted(EXPECT))
    def test_bad_fires_exactly_expected_rule(self, name):
        fs = _ast_findings(FIXDIR / name)
        assert {f.rule for f in fs} == EXPECT[name], \
            [f.format() for f in fs]
        assert all(f.gates for f in fs)

    @pytest.mark.parametrize("name", sorted(EXPECT))
    def test_good_twin_is_clean(self, name):
        fs = _ast_findings(FIXDIR / name.replace("_bad", "_good"))
        assert fs == [], [f.format() for f in fs]

    def test_host_sync_pair_under_hot_path(self):
        # HOST-SYNC only fires under repro/{fl,core,kernels}/ — load the
        # fixture text under a synthetic hot path
        rules = [r for r in _default_rules()
                 if not isinstance(r, SemanticRule)]
        out = {}
        for name in ("host_sync_bad.py", "host_sync_good.py"):
            src = SourceFile.load(str(FIXDIR / name))
            src.path = f"src/repro/core/{name}"
            out[name] = [f for r in rules for f in r.run(src)]
        assert {f.rule for f in out["host_sync_bad.py"]} == {"HOST-SYNC"}
        assert out["host_sync_good.py"] == []

    def test_exc_swallow_pair_under_resilience_surface(self):
        # EXC-SWALLOW only fires under repro/{fl,serve}/ — load the
        # fixture text under a synthetic fl/ path
        from repro.analysis.hygiene import ExcSwallowRule
        rule = ExcSwallowRule()
        out = {}
        for name in ("exc_swallow_bad.py", "exc_swallow_good.py"):
            src = SourceFile.load(str(FIXDIR / name))
            src.path = f"src/repro/fl/{name}"
            out[name] = list(rule.run(src))
        assert len(out["exc_swallow_bad.py"]) == 4, \
            [f.format() for f in out["exc_swallow_bad.py"]]
        assert all(f.rule == "EXC-SWALLOW" and f.gates
                   for f in out["exc_swallow_bad.py"])
        assert out["exc_swallow_good.py"] == [], \
            [f.format() for f in out["exc_swallow_good.py"]]

    def test_exc_swallow_silent_outside_restricted_dirs(self):
        from repro.analysis.hygiene import ExcSwallowRule
        src = SourceFile.load(str(FIXDIR / "exc_swallow_bad.py"))
        src.path = "src/repro/core/exc_swallow_bad.py"
        assert list(ExcSwallowRule().run(src)) == []
        # restrict=() disables the path gate — the corpus harness's knob
        assert len(list(ExcSwallowRule(restrict=()).run(src))) == 4

    def test_three_historical_key_bugs_all_detected(self):
        """The reason this framework exists: the corpus extracted from the
        pre-fix commits of PRs 1, 2 and 4 must never pass the linter."""
        for name, rule in (("pr1_synthesis_bad.py", "KEY-CHAIN"),
                           ("pr2_kmeans_bad.py", "KEY-REUSE"),
                           ("pr4_shard_seeds_bad.py", "KEY-SHARD")):
            fs = _ast_findings(FIXDIR / name)
            assert any(f.rule == rule and f.gates for f in fs), \
                (name, [f.format() for f in fs])


class TestSuppression:
    def test_same_line_disable_collected_but_not_gating(self, tmp_path):
        p = tmp_path / "s.py"
        p.write_text(
            "import jax\n\n\n"
            "def f(key):\n"
            "    a = jax.random.normal(key, (2,))\n"
            "    b = jax.random.uniform(key, (2,))"
            "  # lint: disable=KEY-REUSE\n"
            "    return a + b\n")
        fs = analyze_paths([str(p)], semantic=False)
        assert len(fs) == 1 and fs[0].suppressed and not fs[0].gates
        assert gating(fs) == []

    def test_star_disables_every_rule(self, tmp_path):
        p = tmp_path / "s.py"
        p.write_text(
            "import jax\n\n\n"
            "def f(key):\n"
            "    a = jax.random.normal(key, (2,))\n"
            "    b = jax.random.uniform(key, (2,))  # lint: disable=*\n"
            "    return a + b\n")
        assert gating(analyze_paths([str(p)], semantic=False)) == []


class TestSelfClean:
    def test_ast_gate_zero_unsuppressed(self):
        fs = analyze_paths([str(ROOT / "src" / "repro"),
                            str(ROOT / "benchmarks"),
                            str(ROOT / "examples")], semantic=False)
        assert gating(fs) == [], "\n".join(f.format() for f in gating(fs))

    def test_semantic_gate_on_live_tree(self):
        """Wire contract, Pallas contracts and the retrace grid all hold
        on the imported modules — the acceptance bar for this PR."""
        fs = analyze_paths([str(ROOT / "src" / "repro")], semantic=True)
        assert gating(fs) == [], "\n".join(f.format() for f in gating(fs))


class TestPallasCheckers:
    def _rules(self, call):
        from repro.analysis.pallas_rules import check_call
        return {r for r, _sev, _msg in check_call(call)}

    def test_divisibility_violation_fires(self):
        from repro.analysis.pallas_rules import CapturedCall
        bad = CapturedCall(grid=(4,), inputs=[((10, 256), (3, 256))],
                           outputs=[], scratch_bytes=0)
        assert "PAL-DIV" in self._rules(bad)

    def test_misaligned_lane_block_fires(self):
        from repro.analysis.pallas_rules import CapturedCall
        bad = CapturedCall(grid=(8, 10), inputs=[((512, 960), (64, 96))],
                           outputs=[], scratch_bytes=0)
        assert self._rules(bad) == {"PAL-ALIGN"}

    def test_vmem_budget_warns(self):
        from repro.analysis.pallas_rules import CapturedCall
        big = CapturedCall(grid=(1,),
                           inputs=[((4096, 4096), (4096, 4096))],
                           outputs=[], scratch_bytes=0)
        assert "PAL-VMEM" in self._rules(big)

    def test_tiled_aligned_geometry_is_clean(self):
        from repro.analysis.pallas_rules import CapturedCall
        good = CapturedCall(grid=(4,),
                            inputs=[((512, 512), (128, 512))],
                            outputs=[((512, 512), (128, 512))],
                            scratch_bytes=0)
        assert self._rules(good) == set()
        # a dim-1 block is exempt only where it spans the whole axis
        batchy = CapturedCall(grid=(2, 4), inputs=[((1, 512), (1, 128))],
                              outputs=[], scratch_bytes=0)
        assert self._rules(batchy) == set()

    @pytest.mark.parametrize("B", [16, 100])
    def test_row_block_on_batched_axis_fires(self, B):
        """The fused E-step's old (1, bn) logsumexp block on its (B, Np)
        output: the TPU compiler refuses a second-to-last block dim of 1
        once B > 1, so PAL-ALIGN must too."""
        from repro.analysis.pallas_rules import CapturedCall
        lse_row = CapturedCall(grid=(B, 16, 1), inputs=[],
                               outputs=[((B, 4096), (1, 256))],
                               scratch_bytes=0)
        assert self._rules(lse_row) == {"PAL-ALIGN"}


class TestSanitizer:
    def test_double_consume_raises(self):
        with sanitize(nans=False, infs=False) as st:
            k = jax.random.PRNGKey(123)
            jax.random.normal(k, (2,))
            with pytest.raises(KeyReuseError):
                jax.random.uniform(k, (2,))
        assert st.n_errors == 1

    def test_split_then_draw_is_clean(self):
        with sanitize(nans=False, infs=False) as st:
            ka, kb = jax.random.split(jax.random.PRNGKey(7))
            jax.random.normal(ka, (2,))
            jax.random.normal(kb, (2,))
        assert st.n_errors == 0 and st.n_checked >= 3

    def test_reset_allows_deliberate_replay(self):
        with sanitize(nans=False, infs=False) as st:
            k = jax.random.PRNGKey(5)
            a = jax.random.normal(k, (2,))
            st.reset()
            b = jax.random.normal(k, (2,))
        assert jnp.array_equal(a, b)

    def test_traced_keys_are_skipped(self):
        with sanitize(nans=False, infs=False) as st:
            @jax.jit
            def f(k):
                return jax.random.normal(k, (2,))
            f(jax.random.PRNGKey(1))
        assert st.n_skipped_tracer >= 1 and st.n_errors == 0

    def test_wrappers_and_flags_restored(self):
        import jax.random as jrandom
        before = jrandom.normal
        flag = jax.config.jax_debug_nans
        with sanitize():
            assert jrandom.normal is not before
            assert jax.config.jax_debug_nans is True
        assert jrandom.normal is before
        assert jax.config.jax_debug_nans == flag

    def test_debug_nans_catches_nan(self):
        with sanitize(key_reuse=False):
            with pytest.raises(FloatingPointError):
                jnp.float32(0.0) / jnp.float32(0.0)
