"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
                                            [--json BENCH_<n>.json]

Emits ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit);
``--json PATH`` additionally writes the rows as ``{name: us_per_call}``
JSON so the perf trajectory is machine-readable across PRs.

  frontier          Fig. 4 / Table 5  comm-accuracy frontier, 20 clients
  shifts            Table 2           label/covariate/task extreme shifts
  topology          Fig. 6            5-client linear chain
  gmm_quality       Fig. 7            GMM feature-fit quality (cov × K)
  dp_tradeoff       Fig. 4 DP curves  ε sweep
  reconstruction    Table 3 / Fig. 8  inversion attack ordering
  comm_cost         Eqs. 9-11         cost model + measured wire bytes
  ablations         beyond-paper      EM iters, seeding, wire precision,
                                      heterogeneous per-client K (§6.3)
  synthesize_bench  ISSUE 1/3         looped vs batched server synthesis,
                                      plus the skewed-cohort (1→4096
                                      counts) planner-vs-monolithic A/B
  em_bench          ISSUE 2           fused batched vs reference E-step
  head_bench        ISSUE 5           fused sampler-in-the-loop head vs
                                      pooled on the skewed cohort
  ingest_bench      ISSUE 6           100k-client streaming ingestion:
                                      clients/sec folded + peak resident
                                      bytes vs the stacked-cohort cost
  compile_bench     ISSUE 8           multi-tenant mixed-signature stream:
                                      cold vs warm AOT round-program cache
                                      (launch.aot_cache), no-cache contrast
  chaos_bench       ISSUE 10          fault-injection sweeps: accuracy vs
                                      coverage under drop/corrupt/straggle,
                                      plus the 1000-client wire acceptance
                                      mix (byte conservation + deadline)
  roofline_report   deliverable (g)   dry-run roofline table
  analysis_gate     ISSUE 7           lint wall time + finding counts +
                                      recompile-churn trace grid

``--sanitize`` additionally runs every module under
``repro.analysis.sanitize`` (debug_nans/debug_infs + a non-strict PRNG
key-reuse tracer) and emits per-module ``analysis/sanitize/*`` rows.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import common as C
from repro.launch import compile_cache

MODULES = ["comm_cost", "gmm_quality", "topology", "dp_tradeoff",
           "reconstruction", "shifts", "ablations", "synthesize_bench",
           "em_bench", "head_bench", "ingest_bench", "compile_bench",
           "chaos_bench", "frontier", "roofline_report", "analysis_gate"]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced grids for CI")
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the rows as {name: us_per_call} JSON "
                         "(e.g. BENCH_5.json) for the machine-readable "
                         "perf trajectory")
    ap.add_argument("--sanitize", action="store_true",
                    help="run each module under repro.analysis.sanitize "
                         "(debug_nans/infs + non-strict key-reuse "
                         "tracer); emits analysis/sanitize/<module> rows")
    args = ap.parse_args(argv)
    mods = args.only.split(",") if args.only else MODULES
    compile_cache.enable()

    print("name,us_per_call,derived")
    failures = []
    for name in mods:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["main"])
            if args.sanitize:
                from repro.analysis import sanitize
                with sanitize(strict=False) as st:
                    mod.main(quick=args.quick)
                C.emit(f"analysis/sanitize/{name}", 0.0,
                       f"checked={st.n_checked};reused={st.n_errors};"
                       f"tracer_skipped={st.n_skipped_tracer}")
            else:
                mod.main(quick=args.quick)
            C.emit(f"{name}/__total__", (time.time() - t0) * 1e6, "ok")
        except Exception:
            traceback.print_exc()
            failures.append(name)
            C.emit(f"{name}/__total__", (time.time() - t0) * 1e6, "FAILED")
    if args.json:
        C.write_json(args.json)
    if failures:
        sys.exit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
