"""The benchmark harness: everything that is not one configuration, one
traffic mix or one per-layer metric.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
harness finds the rest by those names, in files of their own:

* ``<config file>`` and the modules beside it (``reference.py``,
  ``flops.py``);
* ``bench/traffic/<traffic>.json``, whose ``kind`` names the driver;
* ``bench/drivers/<kind>.py``, with ``run(ctx) -> Outcome``;
* ``bench/metrics/<metric>.py``, with ``read(obs) -> float | None``.

So a later change adds a configuration, a traffic mix or a per-layer
metric by adding files and entries, and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
WORK_DIR = os.path.join(BENCH_DIR, ".work")
# the persistent compile cache: the directory the environment names, else
# a fixed one inside the checkout
CACHE_DIR = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
             or os.path.join(BENCH_DIR, ".jax_cache"))


class BenchError(Exception):
    """The benchmark cannot run this cell here."""


def load_json(path: str) -> Any:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str):
    """Import a file by path; metric names hold dots, so no import name
    can be derived from them."""
    if not os.path.isfile(path):
        raise BenchError(f"missing file {os.path.relpath(path, ROOT)}")
    name = "bench_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    config_dir: str
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: str = BENCH_DIR

    def config_module(self, name: str):
        return load_module(os.path.join(self.config_dir, name + ".py"))

    def driver(self):
        return load_module(os.path.join(self.bench_dir, "drivers",
                                         self.traffic["kind"] + ".py"))

    def reader(self, metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        metric + ".py")).read


def find_cell(bench: Dict[str, Any], name: str, root: str = ROOT,
              bench_dir: str = BENCH_DIR) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config_path = os.path.join(root, conf["file"])
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(config_path),
                config_dir=os.path.dirname(config_path),
                traffic_name=w["traffic"],
                traffic=load_json(os.path.join(bench_dir, "traffic",
                                               w["traffic"] + ".json")),
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


@dataclasses.dataclass
class RunContext:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float                 # perf_counter() when the process began
    workdir: str
    device_kind: str = ""
    # programs compiled so far (loads from the persistent cache excluded)
    compiles: Callable[[], int] = lambda: 0

    def log(self, msg: str) -> None:
        print(f"[{self.cell.name}] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Check:
    """One number compared with its limit; it passes at or under it."""
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: Dict[str, Check]
    observations: Dict[str, Any]
    memory_peak_bytes: Optional[int]
    trace: Optional[Dict[str, Any]] = None

    @property
    def correct(self) -> bool:
        return (self.failed == 0 and self.attempted > 0
                and all(c.ok for c in self.checks.values()))


def metric_values(cell: Cell, outcome: Outcome, trace: bool
                  ) -> Dict[str, Dict[str, Any]]:
    """The result line's ``metrics``: the cell's end-to-end metrics, or
    with ``trace`` its per-layer metrics as their readers find them (a
    reader that finds nothing leaves its metric out)."""
    out = {}
    if not trace:
        for m in cell.end_to_end:
            if m["name"] not in outcome.end_to_end:
                raise BenchError(f"driver gave no {m['name']}")
            out[m["name"]] = {"value": outcome.end_to_end[m["name"]],
                              "unit": m["unit"]}
        return out
    obs = dict(outcome.observations, trace=outcome.trace)
    for m in cell.per_layer:
        value = cell.reader(m["name"])(obs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def seed_words(seed: int) -> Tuple[int, int]:
    """Two 32-bit words from a seed of any size (the seeds given are
    larger than 32 signed bits hold)."""
    import numpy as np
    a, b = np.random.SeedSequence(seed).generate_state(2)
    return int(a), int(b)


def load_peaks(kind: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    peaks = load_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r}; "
                         f"bench/peaks.json has {sorted(peaks)}")
    return peaks[kind]
