"""The splash attention kernels' share of their roofline in the traced
window: the operations they compute per second, over the least of the
chip's bf16 peak and their arithmetic intensity times its HBM bandwidth
(``bench/peaks.json``).

Time: the device seconds of every operation of the trace's
``breakdown.device_ops`` whose name holds ``splash_mqa`` (the forward,
dq and dkv kernels). Work: the configuration's
``flops.attention_kernel_work`` of one step (only the causal half the
kernels compute), times the window's steps. A configuration without
that function, or a trace whose kept operations hold no such kernel,
reads nothing."""

import os

from bench.harness import BENCH_DIR, load_module, load_peaks

KERNEL = "splash_mqa"


def read(obs):
    tr, cfg = obs.get("trace"), obs.get("config")
    if not tr or not cfg or not obs.get("step_walls"):
        return None
    seconds = sum(s for name, s in tr.get("device_ops") or ()
                  if KERNEL in name)
    path = os.path.join(BENCH_DIR, "configs", cfg.get("name", ""),
                        "flops.py")
    if not seconds or not os.path.isfile(path):
        return None
    work = getattr(load_module(path), "attention_kernel_work", None)
    if work is None:
        return None
    w = work(cfg)
    steps = len(obs["step_walls"])
    peaks = load_peaks(obs["device_kind"])
    roof = min(peaks["bf16_flops_per_s"],
               w["flops"] / w["bytes"] * peaks["hbm_bytes_per_s"])
    return 100.0 * w["flops"] * steps / seconds / roof
