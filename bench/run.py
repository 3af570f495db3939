#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell comes from ``BENCHMARK.json`` at the root of the checkout; its
configuration, traffic, driver and per-layer metric readers are found by
name (see ``bench/harness.py``). The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with the reference beside its limit. The same numbers
end standard error.

A host whose JAX finds no TPU, or fewer chips than the cell asks for,
gets an error and no result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# the checkout's root (for ``bench``) and ``src`` (the system under test);
# the script's own directory would shadow the standard library's ``trace``
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH_DIR]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", os.path.join(BENCH_DIR, ".work",
                                                "tpu_logs"))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Programs compiled (not loaded from the persistent cache) so far."""

    def __init__(self):
        import jax
        self.loaded = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        if event == COMPILE_EVENT:
            self.loaded += 1

    def _event(self, event, **_kw):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def __call__(self) -> int:
        return self.loaded - self.hits


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import (CACHE_DIR, WORK_DIR, BenchError, RunContext,
                               find_cell, load_json, metric_values)

    def fail(msg: str) -> int:
        print(f"bench: {msg}; no result", file=sys.stderr, flush=True)
        return 2

    bench_json = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(bench_json):
        return fail("no BENCHMARK.json at the checkout's root")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail("the system under test (src/repro) is not in this "
                    "checkout")
    try:
        cell = find_cell(load_json(bench_json), args.workload)
    except (BenchError, KeyError, OSError) as e:
        return fail(str(e))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"JAX's device is {devices[0].platform!r}, not a TPU")
    if len(devices) < cell.chips:
        return fail(f"{len(devices)} chips, the cell asks for {cell.chips}")
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = CompileCounter()

    ctx = RunContext(cell=cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=T_START,
                     workdir=os.path.join(WORK_DIR, cell.name),
                     device_kind=devices[0].device_kind, compiles=counter)
    try:
        outcome = cell.driver().run(ctx)
        metrics = metric_values(cell, outcome, bool(args.trace))
    except BenchError as e:
        return fail(str(e))

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": cell.chips,
              "memory_peak_bytes": outcome.memory_peak_bytes}
    result = {"correct": outcome.correct, "attempted": outcome.attempted,
              "failed": outcome.failed, "metrics": metrics, "device": device}
    if args.trace:
        tr = outcome.trace or {}
        if not tr.get("busy_s"):
            return fail("the trace holds no device operation in the window")
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = {k: {"value": c.value, "limit": c.limit}
                        for k, c in outcome.checks.items()}
    print(f"correct={outcome.correct} attempted={outcome.attempted} "
          f"failed={outcome.failed}", file=sys.stderr)
    for k, c in outcome.checks.items():
        print(f"check {k}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
