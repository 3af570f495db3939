"""The harness finds a cell's configuration, traffic mix, driver and
per-layer metrics by name: adding each of them takes new files and
entries only, and no file that is there is edited."""

import hashlib
import json
import os

import pytest

from bench.harness import (BenchError, Check, Outcome, find_cell, load_json,
                           metric_values)
from bench.tests import tiny


def digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def fake_outcome(**obs):
    return Outcome(end_to_end={"train_tokens_per_s": 1.0, "setup_s": 2.0,
                               "recover_s": 3.0},
                   attempted=1, failed=0, checks={"x": Check(0.0, 1.0)},
                   observations=obs, memory_peak_bytes=1)


def test_new_config_traffic_and_metric_need_new_files_only(tmp_path):
    bench = tiny.make_copy(str(tmp_path))
    before = digests(bench)
    # a new traffic mix and a new per-layer metric, as files of their own
    with open(os.path.join(bench, "traffic", "adcc_every4.json"), "w") as fh:
        json.dump(dict(load_json(os.path.join(bench, "traffic",
                                              "adcc_ledger.json")),
                       slot_every=4), fh)
    with open(os.path.join(bench, "metrics", "steps_seen.py"), "w") as fh:
        fh.write("def read(obs):\n"
                 "    walls = obs.get('step_walls')\n"
                 "    return float(len(walls)) if walls else None\n")
    spec_path = os.path.join(str(tmp_path), "BENCHMARK.json")
    spec = load_json(spec_path)
    spec["workloads"].append({"name": "tiny.every4", "config": "tiny",
                              "traffic": "adcc_every4", "chips": 1,
                              "why": "added as data"})
    spec["per_layer"].append({"name": "steps_seen", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "model step",
                              "moves": "train_tokens_per_s",
                              "workloads": ["tiny.every4"]})
    for m in spec["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("tiny.every4")
    cell = find_cell(spec, "tiny.every4", root=str(tmp_path), bench_dir=bench)
    assert cell.config["name"] == "tiny"
    assert cell.traffic["slot_every"] == 4
    assert cell.driver().__name__.endswith("drivers_train_py")
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s",
                                                    "setup_s"]
    got = metric_values(cell, fake_outcome(step_walls={0: 1.0, 1: 1.0}),
                        trace=True)
    assert got["steps_seen"] == {"value": 2.0, "unit": "steps"}
    after = digests(bench)
    assert {k: after[k] for k in before} == before


def test_a_metric_without_workloads_follows_the_metric_it_moves(tmp_path):
    tiny.make_copy(str(tmp_path))
    spec = load_json(os.path.join(str(tmp_path), "BENCHMARK.json"))
    spec["per_layer"].append({"name": "restore_s.everywhere", "unit": "s",
                              "better": "lower", "source": "host_clock",
                              "layer": "recovery", "moves": "recover_s"})
    bench_dir = os.path.join(str(tmp_path), "bench")
    resume = find_cell(spec, "tiny.resume", root=str(tmp_path),
                       bench_dir=bench_dir)
    ledger = find_cell(spec, "tiny.ledger", root=str(tmp_path),
                       bench_dir=bench_dir)
    assert "restore_s.everywhere" in [m["name"] for m in resume.per_layer]
    assert "restore_s.everywhere" not in [m["name"] for m in ledger.per_layer]
    assert {m["name"] for m in ledger.per_layer} == {
        "train_mfu", "idle_pct.train"}


def test_end_to_end_metrics_come_from_the_driver(tmp_path):
    tiny.make_copy(str(tmp_path))
    cell = tiny.context(str(tmp_path), "tiny.resume").cell
    got = metric_values(cell, fake_outcome(), trace=False)
    assert got == {"recover_s": {"value": 3.0, "unit": "s"},
                   "setup_s": {"value": 2.0, "unit": "s"}}
    bare = fake_outcome()
    bare.end_to_end.pop("recover_s")
    with pytest.raises(BenchError):
        metric_values(cell, bare, trace=False)


def test_an_unknown_cell_is_refused():
    spec = load_json(os.path.join(os.path.dirname(tiny.BENCH_DIR),
                                  "BENCHMARK.json"))
    with pytest.raises(BenchError):
        find_cell(spec, "no-such.cell")
