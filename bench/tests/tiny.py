"""A temporary copy of the benchmark with small configurations of the same
model, for CPU rehearsals of the harness and its drivers.

The copy adds files only: configurations ``tiny`` and ``slim`` beside
``mamba2-130m`` (their sizes cut, its modules copied) and a
``BENCHMARK.json`` at the copy's root whose cells use the committed
traffic mixes. ``tiny`` is cut in width and depth and has limits of its
own in its ``limits.json``: the program's readings at that size lie
under them and the faults' far over them. ``slim`` keeps the published
widths with 2 of the 24 layers and a short batch, under the committed
limits.
"""

import copy
import json
import os
import shutil
import time

from bench.harness import BENCH_DIR, RunContext, find_cell, load_json

SOURCE_CONFIG = os.path.join(BENCH_DIR, "configs", "mamba2-130m")
# set from CPU readings at this size: the program's worst grad_gap over a
# few seeds is some 0.004 and the float8 control's some 0.012
TINY_LIMITS = {
    "train": {"loss_gap": 1e-3, "grad_gap": 0.008, "delta_gap": 0.03,
              "ledger_gap": 1e-4},
    "resume": {"restore_gap": 0.0, "loss_gap": 1e-3, "grad_gap": 0.008},
}
# cell: (traffic, the committed cell whose metrics it reports)
CELLS = {"tiny.ledger": ("adcc_ledger", "ledger"),
         "tiny.slot": ("slot_every4", "ledger"),
         "tiny.resume": ("resume_torn", "resume")}
# the published widths with 2 of the 24 layers and a short batch, under
# the committed limits: the size at which the control is tested
SLIM_CELLS = {"slim.ledger": ("adcc_ledger", "ledger"),
              "slim.resume": ("resume_torn", "resume")}
# a traffic mix of the copy's own: ADCC with a slot every 4 steps, the
# window in whole slot periods
SLOT_TRAFFIC = {"kind": "train", "mode": "adcc", "slot_every": 4,
                "n_slots": 3, "warmup_steps": 4, "window_align_steps": 4}


def tiny_config() -> dict:
    cfg = load_json(os.path.join(SOURCE_CONFIG, "config.json"))
    cfg["name"] = "tiny"
    cfg["model"].update(n_layers=2, d_model=64, vocab_size=300, ssm_state=16,
                        ssm_head_dim=16, ssm_chunk=16)
    cfg["shape"] = {"batch": 2, "seq": 32}
    return cfg


def slim_config() -> dict:
    cfg = load_json(os.path.join(SOURCE_CONFIG, "config.json"))
    cfg["name"] = "slim"
    cfg["model"].update(n_layers=2)
    cfg["shape"] = {"batch": 2, "seq": 256}
    return cfg


def add_config(bench: str, cfg: dict, limits_path: str = None) -> None:
    conf = os.path.join(bench, "configs", cfg["name"])
    os.makedirs(conf)
    for name in ("reference.py", "flops.py"):
        shutil.copy(os.path.join(SOURCE_CONFIG, name), conf)
    with open(os.path.join(conf, "config.json"), "w") as fh:
        json.dump(cfg, fh)
    if limits_path:
        shutil.copy(limits_path, os.path.join(conf, "limits.json"))
    else:
        with open(os.path.join(conf, "limits.json"), "w") as fh:
            json.dump(TINY_LIMITS, fh)


def make_copy(root: str) -> str:
    """Copy the benchmark under ``root`` and add the tiny configuration;
    returns the copy's ``bench`` directory."""
    bench = os.path.join(root, "bench")
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        ".work", ".jax_cache", "__pycache__"))
    with open(os.path.join(bench, "traffic", "slot_every4.json"), "w") as fh:
        json.dump(SLOT_TRAFFIC, fh)
    add_config(bench, tiny_config())
    add_config(bench, slim_config(), os.path.join(SOURCE_CONFIG,
                                                  "limits.json"))
    spec = copy.deepcopy(load_json(os.path.join(os.path.dirname(BENCH_DIR),
                                                "BENCHMARK.json")))
    cells = dict(CELLS, **SLIM_CELLS)
    for conf in ("tiny", "slim"):
        spec["configs"].append({"name": conf,
                                "source": "test copy",
                                "file": f"bench/configs/{conf}/config.json",
                                "reduced": ["n_layers"],
                                "why": "CPU rehearsal"})
    for name, (traffic, _) in cells.items():
        spec["workloads"].append({"name": name,
                                  "config": name.split(".")[0],
                                  "traffic": traffic, "chips": 1,
                                  "why": "CPU rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [
                n for n, (_, like) in cells.items()
                if any(w.split(".", 1)[1] == like for w in m["workloads"])]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(spec, fh)
    return bench


def context(root: str, name: str, seed: int = 2 ** 31 + 99,
            seconds: float = 0.5) -> RunContext:
    cell = find_cell(load_json(os.path.join(root, "BENCHMARK.json")), name,
                     root=root, bench_dir=os.path.join(root, "bench"))
    return RunContext(cell=cell, seed=seed, seconds=seconds, trace=False,
                      t_start=time.perf_counter(),
                      workdir=os.path.join(root, "work", name),
                      device_kind="TPU v5 lite")
