"""A temporary copy of the benchmark with a tiny cut of
nemotron3-nano-30b-a3b, for CPU rehearsals of its cell.

The copy is :mod:`bench.tests.tiny`'s, with one configuration added,
``tiny-nemotron``: the committed configuration with every width cut
(the layer pattern ``MEMEM*E``, the groups, the router over more experts
than this chip holds, the shared expert, the donated step and the
training settings kept), its committed ``reference.py`` and ``flops.py``,
and limits of its own; and one cell, ``tiny-nemotron.ledger``, on the
committed ``adcc_ledger`` traffic, in every metric list that names the
committed cell.
"""

import json
import os
import shutil

from bench.harness import BENCH_DIR, load_json
from bench.tests import tiny

SOURCE_CONFIG = os.path.join(BENCH_DIR, "configs", "nemotron3-nano-30b-a3b")
COMMITTED_CELL = "nemotron3-nano-30b-a3b.ledger"
CELL = "tiny-nemotron.ledger"
# set from CPU readings at this size over three seeds: the program's
# worst loss_gap is some 8e-5, grad_gap 0.04, delta_gap 0.024 and
# ledger_gap 1.2e-5 (bfloat16 compute of a 64-wide model); the faults
# read 2e-3 (loss), 0.6 (gradient) and 1e-3 (ledger) and over
TINY_LIMITS = {"train": {"loss_gap": 1e-3, "grad_gap": 0.1,
                         "delta_gap": 0.1, "ledger_gap": 1e-4}}
MODEL = dict(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             vocab_size=300, n_experts=16, experts_per_token=3, moe_d_ff=32,
             shared_d_ff=48, experts_held=4, expert_offset=4, ssm_state=8,
             ssm_heads=8, ssm_head_dim=8, ssm_groups=2, ssm_chunk=16)


def tiny_config() -> dict:
    cfg = load_json(os.path.join(SOURCE_CONFIG, "config.json"))
    cfg["name"] = "tiny-nemotron"
    cfg["model"].update(MODEL)
    cfg["shape"] = {"batch": 2, "seq": 64}
    return cfg


def make_copy(root: str) -> str:
    """:func:`bench.tests.tiny.make_copy`, plus the tiny nemotron cut and
    its cell; returns the copy's ``bench`` directory."""
    bench = tiny.make_copy(root)
    conf = os.path.join(bench, "configs", "tiny-nemotron")
    os.makedirs(conf)
    for name in ("reference.py", "flops.py"):
        shutil.copy(os.path.join(SOURCE_CONFIG, name), conf)
    with open(os.path.join(conf, "config.json"), "w") as fh:
        json.dump(tiny_config(), fh)
    with open(os.path.join(conf, "limits.json"), "w") as fh:
        json.dump(TINY_LIMITS, fh)
    path = os.path.join(root, "BENCHMARK.json")
    spec = load_json(path)
    spec["configs"].append({"name": "tiny-nemotron", "source": "test copy",
                            "file": "bench/configs/tiny-nemotron/config.json",
                            "reduced": ["num_hidden_layers"],
                            "why": "CPU rehearsal"})
    spec["workloads"].append({"name": CELL, "config": "tiny-nemotron",
                              "traffic": "adcc_ledger", "chips": 1,
                              "why": "CPU rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if COMMITTED_CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + [CELL]
    with open(path, "w") as fh:
        json.dump(spec, fh)
    return bench


context = tiny.context
