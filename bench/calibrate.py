#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at a cell's size.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control 3 --faults 3

For each seed, the program's numbers against the plain reference, as the
cell's driver computes them (the program's first steps, or a resumed
step, through the trainer's own ``run``). For the first ``--control``
seeds, the control: the reference computed with the operands of its
matrix products in the configuration's ``control_dtype``, put in the
program's place. For the first ``--faults`` seeds, the program with each
fault of ``bench/faults.py`` that the cell can have planted under its
step.

Prints one JSON line per reading and a summary: per number the largest
program reading (the lower end of the limit), the smallest control and
fault readings (the upper end). The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH_DIR]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", os.path.join(BENCH_DIR, ".work",
                                                "tpu_logs"))


def train_readings(cell, seeds, n_control, n_faults, out):
    import jax.numpy as jnp
    from bench import training as T
    from bench.drivers import train as D
    from bench.faults import FAULTS
    from repro.core.acc_state import ChecksumLedger

    tr, cfg = cell.traffic, cell.config
    workdir = os.path.join(BENCH_DIR, ".work", "calibrate")
    trainer = T.build_trainer(cfg, workdir, seeds[0], mode=tr["mode"],
                              slot_every=tr["slot_every"],
                              n_slots=tr["n_slots"])
    step_fn = trainer.step_fn
    faults = [(name, make(trainer)) for name, make in sorted(FAULTS.items())]
    reference = cell.config_module("reference")
    for i, seed in enumerate(seeds):
        sides = [("program", step_fn)] + (faults if i < n_faults else [])
        source = T.BatchSource(seed, trainer.batch, trainer.seq,
                               cfg["model"]["vocab_size"])
        ref = D.reference_readings(cell, seed, source)
        for name, fn in sides:
            trainer.ledger = ChecksumLedger(
                os.path.join(workdir, f"ledger_{seed}_{name}.jsonl"))
            params = T.make_weights(reference, cfg, seed)
            first, _ = D.first_steps(cfg, trainer, params, source,
                                     step_fn=fn)
            del params
            trainer.run(D.COMPARED_STEPS, log_every=0)
            out(name, seed, T.compare(
                first.readings(T.make_weights(reference, cfg, seed),
                               trainer.ledger.path), ref))
        if i < n_control:
            ctl = D.reference_readings(
                cell, seed, source,
                operand_dtype=jnp.dtype(cfg["control_dtype"]))
            out("control", seed, T.compare(ctl, ref))


def resume_readings(cell, seeds, n_control, n_faults, out):
    import jax.numpy as jnp
    from bench.drivers import resume as D
    from bench.faults import FAULTS, RESUME_FAULTS

    workdir = os.path.join(BENCH_DIR, ".work", "calibrate")
    for i, seed in enumerate(seeds):
        ledger = D.build_image(cell, workdir, seed)
        D.cycle(cell, workdir, seed, ledger)          # load or compile
        ref = D.reference_readings(cell, seed)
        wraps = [("program", None)] + (
            [(f, FAULTS[f]) for f in RESUME_FAULTS] if i < n_faults else [])
        for name, wrap in wraps:
            held = D.Held()
            c = D.cycle(cell, workdir, seed, ledger, held, wrap=wrap)
            gap, prog = D.program_readings(cell, seed, held)
            got = dict(D.T.compare(prog, ref), restore_gap=gap)
            out(name, seed, dict(got, ok=c["ok"]))
        if i < n_control:
            ctl = D.reference_readings(
                cell, seed, operand_dtype=jnp.dtype(cell.config["control_dtype"]))
            out("control", seed, D.T.compare(ctl, ref))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--json", default=None, help="also write the readings here")
    args = ap.parse_args(argv)

    import jax
    from bench.harness import CACHE_DIR, find_cell, load_json

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = find_cell(load_json(os.path.join(ROOT, "BENCHMARK.json")),
                     args.workload)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    rows = []

    def out(side, seed, numbers):
        row = {"side": side, "seed": seed, **numbers}
        rows.append(row)
        print(json.dumps(row), flush=True)

    kind = cell.traffic["kind"]
    {"train": train_readings, "resume": resume_readings}[kind](
        cell, seeds, args.control, args.faults, out)
    keys = [k for k in rows[0] if k not in ("side", "seed", "ok")]
    summary = {"device": jax.devices()[0].device_kind,
               "workload": args.workload}
    for k in keys:
        for side in sorted({r["side"] for r in rows}):
            vals = [r[k] for r in rows if r["side"] == side and k in r]
            if vals:
                summary[f"{k}.{side}"] = (max if side == "program"
                                          else min)(vals)
    print(json.dumps({"summary": summary}), flush=True)
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)), exist_ok=True)
        with open(args.json, "w") as fh:
            json.dump({"rows": rows, "summary": summary}, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
