"""The per-layer metric readers and the peaks table."""

import os

import pytest

from bench.harness import BENCH_DIR, BenchError, load_module, load_peaks

MFU_FLOPS = 861e6


def reader(name):
    return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py")).read


def test_peaks_know_v5e_and_refuse_other_kinds():
    peaks = load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(BenchError):
        load_peaks("cpu")


def test_train_mfu():
    obs = {"device_kind": "TPU v5 lite", "tokens_per_s": 36_000.0,
           "flops_per_token": MFU_FLOPS}
    assert reader("train_mfu")(obs) == pytest.approx(
        100 * 36_000 * MFU_FLOPS / 197e12)
    assert reader("train_mfu")({"restore_s": [1.0]}) is None


def test_train_mfu_refuses_an_unknown_device():
    with pytest.raises(BenchError):
        reader("train_mfu")({"device_kind": "TPU v9", "tokens_per_s": 1.0,
                             "flops_per_token": 1.0})


@pytest.mark.parametrize("name, obs_key", [("idle_pct.train", "tokens_per_s"),
                                           ("idle_pct.resume", "restore_s")])
def test_idle_share(name, obs_key):
    obs = {obs_key: 1.0, "trace": {"busy_s": 3.0, "window_s": 4.0}}
    assert reader(name)(obs) == pytest.approx(25.0)
    assert reader(name)({obs_key: 1.0, "trace": None}) is None
    assert reader(name)({"trace": {"busy_s": 3.0, "window_s": 4.0}}) is None


@pytest.mark.parametrize("name", ["restore_s", "resume_step_s"])
def test_resume_phases(name):
    assert reader(name)({name: [1.0, 2.0, 3.0]}) == pytest.approx(2.0)
    assert reader(name)({}) is None
