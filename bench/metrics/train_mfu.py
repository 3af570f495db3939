"""Model FLOP/s utilization of the training step: the window's tokens per
second times the operations one token requires (the configuration's
``flops.py``, rematerialization excluded), over the chip's bf16 peak
from ``bench/peaks.json``."""

from bench.harness import load_peaks


def read(obs):
    if "flops_per_token" not in obs:
        return None
    peak = load_peaks(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * obs["tokens_per_s"] * obs["flops_per_token"] / peak
