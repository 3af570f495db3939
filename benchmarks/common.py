"""Shared benchmark utilities: timing, CSV emission, cost models.

Runtime-overhead figures (paper Figs. 4/8/13) report
``(native_compute + modeled_mechanism_seconds) / native_compute``:
compute time is measured wall-clock on this host, mechanism cost is
charged through the NVM emulator's bandwidth model (NVM = DRAM/8,
Quartz-style — paper §III.A). Recomputation/correctness figures
(Figs. 3/7/10/12) run the real crash emulator end to end.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, Dict, List, Optional

ART = os.path.join(os.path.dirname(__file__), "artifacts")


@dataclasses.dataclass
class Row:
    name: str
    value: float
    derived: str = ""

    def csv(self) -> str:
        return f"{self.name},{self.value:.6g},{self.derived}"


def write_json(path: str, payload) -> None:
    """Shared machine-readable artifact writer (suite artifacts, the
    ``--json`` combined output, and the scenario sweep all use the same
    underlying writer — repro.scenarios.driver.dump_json)."""
    from repro.scenarios.driver import dump_json

    dump_json(path, payload)


def rows_to_records(rows: List[Row]) -> List[Dict]:
    return [dataclasses.asdict(r) for r in rows]


def emit(rows: List[Row], save_as: Optional[str] = None) -> None:
    for r in rows:
        print(r.csv(), flush=True)
    if save_as:
        write_json(os.path.join(ART, save_as), rows_to_records(rows))


def dense_figure_cli(run_fn: Callable, artifact: str, argv=None) -> None:
    """Shared ``__main__`` entry for the dense-matrix figure suites
    (fig3/fig7/fig_torn): ``--smoke`` + ``--workers`` + ``--mode`` flags
    over a ``run(smoke=, workers=, mode=)`` suite function. With
    ``--mode batched`` the matrix is evaluated by the batched engine and
    the suites' gate stack pins it cell-for-cell against a fresh
    measure-mode sweep."""
    import argparse

    from repro.launch.compile_cache import enable_compile_cache
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI size axis (gates run at every size)")
    ap.add_argument("--workers", type=int, default=None,
                    help="processes for the sweep "
                         "(default: REPRO_SWEEP_WORKERS or 2)")
    ap.add_argument("--mode", default="measure",
                    choices=["measure", "batched"],
                    help="cell evaluation mode (default: measure)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    emit(run_fn(smoke=args.smoke or None, workers=args.workers,
                mode=args.mode),
         save_as=artifact)


def timeit(fn: Callable, repeats: int = 3) -> float:
    """Best-of wall time."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best
