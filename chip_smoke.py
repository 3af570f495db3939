#!/usr/bin/env python3
"""Chip smoke test: the repository's two TPU paths, end to end, on a TPU.

    python chip_smoke.py             # one chip: sweep, ABFT kernel, trainer
    python chip_smoke.py --chips 4   # four chips: the FSDP trainer only

One chip runs three phases, each through the entry points a user calls:

1. sweep — ``sweep(engine="fork", mode="batched", workers=1)`` on the
   ``device`` emulator backend over the pairs whose evaluators launch
   device math, at the full sizes of their figures: fig_torn's
   ``(cg, adcc)`` / ``(mm, adcc)`` and fig_kv's ``etc`` / ``udb``
   profiles under ``adcc``. Every cell must equal the host
   ``mode="measure"`` cell on the ``vectorized`` backend, no cell may
   fall back to per-cell evaluation, every kernel the pair leans on
   must have launched, and every launch must have landed on the chip.
2. abft — the fused-checksum Pallas matmul at 4096x4096 f32 against
   its jnp reference, and checksum verification before and after one
   corrupted element.
3. trainer — ``ADCCTrainer`` on full-width mamba2-130m (batch 8, seq
   1024): an uncrashed run, and a run crashed at step 9 that fresh
   trainers resume from the verified slot at step 7; the resumed losses
   and final parameters must equal the uncrashed run's bit for bit.

``--chips 4`` runs only the trainer on a (data=4, model=1) FSDP mesh at
global batch 32 (seq 512), crashed and resumed the same way, against an uncrashed
one-device run of the same configuration on the first device: losses
within :data:`MESH_LOSS_RTOL`, and each device holding about a quarter
of the parameter and optimizer bytes.

Each phase prints its own lines. The last line of a passing run is
``{"ok": true, "device": {...}}``; a failing phase, or a host whose
default JAX device is not a TPU, exits non-zero without it.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))
sys.path.insert(0, HERE)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# the trainer's crash/resume workdir: fixed, under the checkout, cleaned
# at the start of every trainer phase (gitignored)
WORKDIR = os.path.join(HERE, ".smoke_work")

# the device kernels whose launches the sweep phase reports, and those
# each covered pair must launch. The device backend's forward-pass
# kernels (cache_op_update, queue_validity) take only spans of at least
# DeviceBackend.MIN_DEVICE_ENTRIES entries; no region of these figure
# sizes is that large, so they are reported and not required.
DEVICE_KERNELS = ("cg_errors", "mm_stats", "kv_row_checksums",
                  "kv_value_match", "cache_op_update", "queue_validity")
SWEEP_KERNELS = {
    "cg": ("cg_errors",),
    "mm": ("mm_stats",),
    "kv": ("kv_row_checksums", "kv_value_match"),
}

ABFT_N = 4096
# kernel C vs a highest-precision XLA reference, relative to max |C|
ABFT_RTOL = 1e-3

TRAIN_ARCH = "mamba2-130m"
TRAIN_BATCH = 8
TRAIN_SEQ = 1024
TRAIN_STEPS = 12
SLOT_EVERY = 4
CRASH_AT = 9

MESH_CHIPS = 4
MESH_BATCH = 32
# the one-device reference holds the whole global batch: at seq 1024 its
# step needs 16.6 GB by the compiler's count (f32 logits over the 50k
# vocabulary dominate), more than one chip's 16 GB, so the 4-chip path
# runs seq 512 with full rematerialization on both sides (10.3 GB)
MESH_SEQ = 512
MESH_REMAT = "full"
# sharded vs one-device losses: bf16 compute with a different reduction
# order (per-shard partial sums, then all-reduce) moves the loss in its
# last bf16 bits; 12 steps keep the drift far below this bound
MESH_LOSS_RTOL = 1e-2
# each device's share of parameter + optimizer bytes on the 4-way mesh
# (a quarter, plus the small leaves that stay replicated)
MESH_SHARE_RANGE = (0.2, 0.3)


class PhaseFailure(AssertionError):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailure(msg)


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# phase 1: batched sweep on the device
# ---------------------------------------------------------------------------

def figure_pairs():
    """(label, workload spec, plans) at the figures' full sizes."""
    from benchmarks import fig_kv, fig_torn

    torn_plans = fig_torn._plans(fig_torn.FRACTIONS, fig_torn.SAMPLES)
    kv_plans = fig_kv._plans(fig_kv.FRACTIONS)
    cg, mm = fig_torn.WORKLOADS[:2]
    etc, udb = fig_kv.WORKLOADS[:2]
    return [("cg", cg, torn_plans), ("mm", mm, torn_plans),
            ("kv/etc", etc, kv_plans), ("kv/udb", udb, kv_plans)]


def sweep_phase(pairs, cache_bytes: int = 1024 * 1024) -> dict:
    """Batched sweep on the device backend vs host measure sweep, per
    pair. Returns per-pair cell counts, seconds and launch counts."""
    from benchmarks.scenarios_sweep import full_divergences
    from repro.core.backends.batched import LAUNCHES
    from repro.core.nvm import NVMConfig
    from repro.scenarios import sweep

    platform = jax.devices()[0].platform
    host = NVMConfig(cache_bytes=cache_bytes, backend="vectorized")
    dev = NVMConfig(cache_bytes=cache_bytes, backend="device")
    out = {}
    for label, wl, plans in pairs:
        kw = dict(workloads=(wl,), strategies=("adcc",), plans=plans,
                  engine="fork", workers=1)
        t0 = time.perf_counter()
        measure = sweep(mode="measure", cfg=host, **kw)
        t_measure = time.perf_counter() - t0
        before = LAUNCHES.copy()
        t0 = time.perf_counter()
        batched = sweep(mode="batched", cfg=dev, **kw)
        t_batched = time.perf_counter() - t0
        launches = {k: v - before[k] for k, v in LAUNCHES.items()
                    if v - before[k]}
        by_kernel = {f"{k}@{platform}": launches.get((k, platform), 0)
                     for k in DEVICE_KERNELS}
        by_kernel.update({f"{k}@{p}": n for (k, p), n in launches.items()
                          if p != platform})
        fallbacks = sum(1 for c in batched if "batched_fallback" in c.info)
        div = full_divergences(batched, measure)
        _say("sweep", f"{label}: cells batched={len(batched)} "
                      f"measure={len(measure)} fallback={fallbacks} "
                      f"divergent={len(div)} seconds batched={t_batched:.2f} "
                      f"measure={t_measure:.2f} launches={by_kernel}")
        _check(len(batched) == len(measure) > 0,
               f"{label}: cell counts differ or are zero")
        _check(fallbacks == 0, f"{label}: {fallbacks} batched_fallback cells")
        _check(not div, f"{label}: batched != measure: {div[:3]}")
        off = {k: n for k, n in launches.items() if k[1] != platform}
        _check(not off, f"{label}: launches off the {platform}: {off}")
        for kernel in SWEEP_KERNELS[label.split("/")[0]]:
            _check(launches.get((kernel, platform), 0) > 0,
                   f"{label}: no {kernel} launch")
        out[label] = {"cells": len(batched), "batched_seconds": t_batched,
                      "measure_seconds": t_measure, "launches": by_kernel}
    return out


# ---------------------------------------------------------------------------
# phase 2: fused-checksum ABFT matmul kernel
# ---------------------------------------------------------------------------

def abft_phase(n: int = ABFT_N, seed: int = 0) -> dict:
    from repro.kernels.abft_matmul.ops import abft_matmul_full
    from repro.kernels.abft_matmul.ref import abft_encode_full_ref
    from repro.kernels.checksum_verify.ops import verify_checksums

    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, (n, n), jnp.float32)
    b = jax.random.normal(kb, (n, n), jnp.float32)
    t0 = time.perf_counter()
    cf = abft_matmul_full(a, b).block_until_ready()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    cf = abft_matmul_full(a, b).block_until_ready()
    warm = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        ref = abft_encode_full_ref(a, b)
    scale = float(jnp.max(jnp.abs(ref)))
    rel = float(jnp.max(jnp.abs(cf - ref))) / scale
    ok, _, _ = verify_checksums(cf)
    i, j = n // 3, (2 * n) // 5
    bad = cf.at[i, j].add(scale)
    ok_bad, rres, cres = verify_checksums(bad)
    hit = (int(jnp.argmax(jnp.abs(rres))), int(jnp.argmax(jnp.abs(cres))))
    _say("abft", f"n={n} f32 first_call_seconds={first:.3f} "
                 f"warm_call_seconds={warm:.4f} max_rel_err_vs_ref={rel:.3e} "
                 f"clean_verifies={bool(ok)} corrupted_verifies={bool(ok_bad)} "
                 f"flagged_row_col={hit} injected={(i, j)}")
    _check(cf.shape == (n + 1, n + 1), f"C_f shape {cf.shape}")
    _check(bool(jnp.all(jnp.isfinite(cf))), "non-finite C_f")
    _check(rel <= ABFT_RTOL, f"kernel vs reference rel err {rel:.3e}")
    _check(bool(ok), "clean full-checksum matrix failed verification")
    _check(not bool(ok_bad), "corrupted element was not detected")
    _check(hit == (i, j), f"corruption located at {hit}, injected {(i, j)}")
    return {"n": n, "max_rel_err": rel, "warm_call_seconds": warm}


# ---------------------------------------------------------------------------
# phase 3: ADCC trainer, crash and resume
# ---------------------------------------------------------------------------

def _train_cfg(steps: int):
    from repro.configs.base import TrainConfig
    return TrainConfig(total_steps=steps, warmup_steps=4)


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _max_abs_diff(a, b) -> float:
    diffs = jax.tree.map(
        lambda x, y: float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                           - y.astype(jnp.float32)))), a, b)
    return max(jax.tree.leaves(diffs))


def _slot_states(trainer) -> list:
    return [(k, trainer.store.read_meta(k))
            for k in range(trainer.store.n_slots)]


def _crash_and_resume(cfg, tcfg, workdir, *, batch, seq, steps, mesh=None):
    """Train, crash at CRASH_AT, and resume in a fresh trainer.

    The asynchronous slot writer has no fence, so at full width the step
    loop can reach CRASH_AT while every slot is still being written; the
    crash then tears them all and recovery rightly restarts from
    scratch. To resume from a verified slot every time, the first leg
    trains up to the last slot boundary before the crash and ends
    normally (``run`` drains the writer); the second resumes, trains on
    and crashes at CRASH_AT; the third resumes again and finishes.
    Returns (losses of steps 0..steps-1, finishing trainer, its result,
    seconds the first leg waited for its slots after its last step)."""
    from repro.launch.train import ADCCTrainer

    kw = dict(batch=batch, seq=seq, slot_every=SLOT_EVERY, mode="adcc",
              mesh=mesh)
    boundary = (CRASH_AT // SLOT_EVERY) * SLOT_EVERY
    first = ADCCTrainer(cfg, tcfg, workdir, **kw)
    t0 = time.perf_counter()
    r1 = first.run(boundary, log_every=0)
    drain = time.perf_counter() - t0 - sum(r1.step_seconds)
    del first
    gc.collect()
    crashed = ADCCTrainer(cfg, tcfg, workdir, **kw)
    r2 = crashed.run(steps, crash_at_step=CRASH_AT, log_every=0)
    slots = _slot_states(crashed)
    del crashed
    gc.collect()
    resumed = ADCCTrainer(cfg, tcfg, workdir, **kw)
    r3 = resumed.run(steps, log_every=0)
    for r in (r2, r3):
        _check(r.resumed_from == boundary - 1
               and "verified" in r.recovery_report,
               f"resume did not come from the verified slot at step "
               f"{boundary - 1}: {r.recovery_report}; slots {slots}")
    _check(r2.losses == r3.losses[:len(r2.losses)],
           "re-run steps after the crash differ from the crashed run's")
    return r1.losses + r3.losses, resumed, r3, drain


def trainer_phase(cfg, *, batch: int = TRAIN_BATCH, seq: int = TRAIN_SEQ,
                  steps: int = TRAIN_STEPS, workdir: str = WORKDIR) -> dict:
    from repro.launch.train import ADCCTrainer

    shutil.rmtree(workdir, ignore_errors=True)
    tcfg = _train_cfg(steps)
    ref = ADCCTrainer(cfg, tcfg, os.path.join(workdir, "ref"), batch=batch,
                      seq=seq, slot_every=SLOT_EVERY, mode="none")
    r_ref = ref.run(steps, log_every=0)
    ref_params = ref._final_params
    del ref
    gc.collect()
    times = r_ref.step_seconds
    warm = sorted(times[2:])
    median = warm[len(warm) // 2]
    losses, resumed, r2, drain = _crash_and_resume(
        cfg, tcfg, os.path.join(workdir, "crash"), batch=batch, seq=seq,
        steps=steps)
    same_losses = losses == r_ref.losses
    pdiff = _max_abs_diff(ref_params, resumed._final_params)
    peak = _peak_bytes(jax.devices()[0])
    _say("trainer", f"{cfg.name} {cfg.param_count() / 1e6:.1f}M params "
                    f"batch={batch} seq={seq} steps={steps} "
                    f"first_step_seconds={times[0]:.2f} "
                    f"compile_seconds~{times[0] - median:.2f} "
                    f"median_step_seconds={median:.4f} "
                    f"peak_bytes_in_use={peak}")
    _say("trainer", f"crash_at={CRASH_AT} resumed_from={r2.resumed_from} "
                    f"({r2.recovery_report}) losses_bitwise_equal="
                    f"{same_losses} final_param_max_abs_diff={pdiff} "
                    f"slot_drain_wait_seconds={drain:.2f}")
    _say("trainer", f"loss {r_ref.losses[0]:.5f} -> {r_ref.losses[-1]:.5f}")
    _check(all(np.isfinite(r_ref.losses)), "non-finite loss")
    _check(same_losses, f"resumed losses {losses} != uncrashed "
                        f"{r_ref.losses}")
    _check(pdiff == 0.0, f"resumed final params differ by {pdiff}")
    shutil.rmtree(workdir, ignore_errors=True)
    return {"median_step_seconds": median, "first_step_seconds": times[0],
            "peak_bytes_in_use": peak, "resumed_from": r2.resumed_from}


def _device_shares(tree):
    """(share of ``tree``'s logical bytes held by each device, stored
    bytes over logical bytes)."""
    per = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            per[shard.device.id] = (per.get(shard.device.id, 0)
                                    + shard.data.nbytes)
    total = sum(per.values())
    logical = sum(leaf.nbytes for leaf in jax.tree.leaves(tree))
    return [per[d] / logical for d in sorted(per)], total / logical


def mesh_trainer_phase(cfg, *, n_chips: int = MESH_CHIPS,
                       batch: int = MESH_BATCH, seq: int = MESH_SEQ,
                       steps: int = TRAIN_STEPS,
                       workdir: str = WORKDIR) -> dict:
    from repro.launch.mesh import make_mesh
    from repro.launch.train import ADCCTrainer

    shutil.rmtree(workdir, ignore_errors=True)
    devices = jax.devices()
    _check(len(devices) >= n_chips, f"{len(devices)} devices < {n_chips}")
    tcfg = dataclasses.replace(_train_cfg(steps), fsdp=True,
                               remat=MESH_REMAT)
    mesh = make_mesh((n_chips, 1), ("data", "model"),
                     devices=devices[:n_chips])
    t0 = time.perf_counter()
    losses, resumed, r2, drain = _crash_and_resume(
        cfg, tcfg, os.path.join(workdir, "mesh"), batch=batch, seq=seq,
        steps=steps, mesh=mesh)
    mesh_seconds = time.perf_counter() - t0
    shares, stored = _device_shares(
        {"params": resumed._final_params, "opt": resumed._final_opt})
    times = sorted(r2.step_seconds[1:])
    median = times[len(times) // 2] if times else float("nan")
    del resumed
    gc.collect()
    one = make_mesh((1, 1), ("data", "model"), devices=devices[:1])
    ref = ADCCTrainer(cfg, tcfg, os.path.join(workdir, "one"), batch=batch,
                      seq=seq, slot_every=SLOT_EVERY, mode="none", mesh=one)
    r_one = ref.run(steps, log_every=0)
    rel = max(abs(a - b) / abs(b) for a, b in zip(losses, r_one.losses))
    _say("trainer4", f"{cfg.name} mesh=(data={n_chips}, model=1) fsdp "
                     f"global_batch={batch} seq={seq} remat={tcfg.remat} "
                     f"steps={steps} "
                     f"crash_at={CRASH_AT} resumed_from={r2.resumed_from} "
                     f"({r2.recovery_report}) crash+resume_seconds="
                     f"{mesh_seconds:.2f} resumed_median_step_seconds="
                     f"{median:.4f} slot_drain_wait_seconds={drain:.2f} "
                     f"peak_bytes_in_use="
                     f"{[_peak_bytes(d) for d in devices[:n_chips]]}")
    _say("trainer4", f"per-device share of param+opt bytes="
                     f"{[round(s, 4) for s in shares]} stored/logical="
                     f"{stored:.4f}")
    _say("trainer4", f"losses mesh={[round(x, 6) for x in losses]}")
    _say("trainer4", f"losses one-device={[round(x, 6) for x in r_one.losses]}"
                     f" max_rel_diff={rel:.3e} (tolerance {MESH_LOSS_RTOL})")
    _check(len(shares) == n_chips, f"state on {len(shares)} devices")
    lo, hi = MESH_SHARE_RANGE
    _check(all(lo <= s <= hi for s in shares),
           f"per-device state shares {shares} outside [{lo}, {hi}]")
    _check(len(losses) == len(r_one.losses) == steps, "loss count")
    _check(all(np.isfinite(losses)), "non-finite loss")
    _check(rel <= MESH_LOSS_RTOL, f"mesh vs one-device losses {rel:.3e}")
    shutil.rmtree(workdir, ignore_errors=True)
    return {"shares": shares, "max_rel_loss_diff": rel}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, MESH_CHIPS), default=1,
                    help="1: sweep, ABFT kernel and trainer on one chip; "
                         f"{MESH_CHIPS}: only the FSDP trainer on a "
                         f"{MESH_CHIPS}-chip mesh")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: default JAX device is {dev.platform!r}, not a "
              "TPU; nothing was run", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    _say("setup", f"device={dev.device_kind} count={len(jax.devices())} "
                  f"jax={jax.__version__} compile_cache={cache}")

    from repro.models.registry import get_config
    cfg = get_config(TRAIN_ARCH)
    t0 = time.perf_counter()
    if args.chips == 1:
        sweep_phase(figure_pairs())
        _say("sweep", f"phase_seconds={time.perf_counter() - t0:.1f}")
        t1 = time.perf_counter()
        abft_phase()
        _say("abft", f"phase_seconds={time.perf_counter() - t1:.1f}")
        t1 = time.perf_counter()
        trainer_phase(cfg)
        _say("trainer", f"phase_seconds={time.perf_counter() - t1:.1f}")
    else:
        mesh_trainer_phase(cfg)
        _say("trainer4", f"phase_seconds={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
