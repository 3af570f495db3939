"""Benchmark driver: one module per paper table/figure + framework
tables. Prints ``name,value,derived`` CSV; ``--json PATH`` additionally
writes every suite's rows as one machine-readable artifact.

    python -m benchmarks.run                      # every suite
    python -m benchmarks.run fig4 fig8 fig13      # just these
    python -m benchmarks.run --backend reference scenarios

  fig3      CG recomputation, every crash step        (paper Fig. 3)
  fig4      CG runtime, 7 mechanisms                  (paper Fig. 4)
  fig7      ABFT-MM recomputation, every crash step   (paper Fig. 7)
  fig8      ABFT-MM runtime vs rank, 7 mechanisms     (paper Fig. 8)
  fig10_12  MC correctness basic vs selective restart (paper Figs. 10+12)
  fig13     MC runtime, 7 mechanisms                  (paper Fig. 13)
  fig_torn  torn-write detection coverage vs survival (BENCH_torn.json)
  fig_faults nested-crash + media-fault campaigns     (BENCH_faults.json)
  fig_kv    KV serving durability vs overhead matrix  (BENCH_kv.json)
  scenarios workload x strategy x crash-point sweep   (BENCH_scenarios.json)
  sweep     rerun/fork/measure sweep timing + gates   (BENCH_sweep.json)
  train     training-loop ADCC vs sync checkpoint     (beyond-paper)
  kernel    ABFT matmul fused-checksum overhead       (kernel-level)

Suites construct their NVMConfigs lazily (inside ``run()``), so
``--backend`` / ``REPRO_NVM_BACKEND`` can never be snapshotted at import
time and silently ignored. ``--smoke`` / ``--workers`` export
``REPRO_SCENARIOS_SMOKE`` / ``REPRO_SWEEP_WORKERS`` the same way, for
the suites that sweep scenario matrices (fig3, fig7, fig_torn,
fig_faults, fig_kv, scenarios, sweep). ``fig_faults --chaos`` (direct
invocation) additionally gates the self-healing pool against injected
worker kills and hangs.

Roofline (reads dry-run artifacts): ``python -m benchmarks.roofline``.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

from repro.launch.compile_cache import enable_compile_cache

from . import (fig3_cg_recompute, fig4_cg_runtime, fig7_mm_recompute,
               fig8_mm_runtime, fig10_12_mc_correctness, fig13_mc_runtime,
               fig_faults, fig_kv, fig_torn, kernel_bench, scenarios_sweep,
               sweep_timing, train_overhead)
from .common import emit, rows_to_records, write_json

SUITES = {
    "fig3": fig3_cg_recompute,
    "fig4": fig4_cg_runtime,
    "fig7": fig7_mm_recompute,
    "fig8": fig8_mm_runtime,
    "fig10_12": fig10_12_mc_correctness,
    "fig13": fig13_mc_runtime,
    "fig_torn": fig_torn,
    "fig_faults": fig_faults,
    "fig_kv": fig_kv,
    "scenarios": scenarios_sweep,
    "sweep": sweep_timing,
    "train": train_overhead,
    "kernel": kernel_bench,
}
SUITE_NAMES = tuple(SUITES)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("suites", nargs="*", metavar="SUITE",
                    help=f"suites to run (default: all; one of {SUITE_NAMES})")
    ap.add_argument("--only", default=None, choices=list(SUITE_NAMES),
                    help="(legacy) run a single suite")
    ap.add_argument("--backend", default=None,
                    choices=["reference", "vectorized"],
                    help="NVM emulation backend for every suite "
                         "(default: NVMConfig's default, i.e. vectorized)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write all executed suites' rows to PATH as JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized scenario matrices "
                         "(exports REPRO_SCENARIOS_SMOKE=1)")
    ap.add_argument("--workers", type=int, default=None, metavar="N",
                    help="processes for scenario sweeps "
                         "(exports REPRO_SWEEP_WORKERS)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.backend:
        os.environ["REPRO_NVM_BACKEND"] = args.backend
    if args.smoke:
        os.environ["REPRO_SCENARIOS_SMOKE"] = "1"
    if args.workers is not None:
        os.environ["REPRO_SWEEP_WORKERS"] = str(args.workers)
    unknown = [s for s in args.suites if s not in SUITES]
    if unknown:
        ap.error(f"unknown suite(s) {unknown}; choose from {SUITE_NAMES}")
    names = list(args.suites) or ([args.only] if args.only
                                  else list(SUITE_NAMES))
    print("name,value,derived")
    t0 = time.time()
    by_suite = {}
    for name in names:
        print(f"# --- {name} ---", flush=True)
        mod = SUITES[name]
        rows = mod.run()
        emit(rows, save_as=getattr(mod, "ARTIFACT", None))
        by_suite[name] = rows_to_records(rows)
    if args.json:
        write_json(args.json, {"schema": "benchmarks.run/v1",
                               "backend": args.backend or "default",
                               "suites": by_suite})
    print(f"# total {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
