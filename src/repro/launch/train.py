"""ADCC trainer + launcher (``python -m repro.launch.train --arch ...``).

Per step the trainer:
  1. pulls batch t from the counter-based pipeline (pure function of t),
  2. runs the jitted train_step (params/opt sharded per partition rules),
  3. synchronously appends the few-KB checksum ledger record — the
     paper's "flush one cache line per iteration" — its loss and
     checksums fetched from the device in one transfer,
  4. every ``slot_every`` steps enqueues the heavy state to the async,
     fence-free slot writer (torn on crash, like cache-eviction residue).

On start it attempts ADCC recovery: ledger linearity-chain validation,
then newest-first slot scan with per-tensor checksum verification
(core/acc_state.py). Restores the data cursor + RNG with the accepted
step, making recovery bitwise-reproducible — asserted by the
crash/restart integration test.

Also includes the step-time straggler monitor (flags slow hosts for the
controller to replace — simulated single-host here, interface real).

A family that routes tokens to experts reports its routing counts in the
step's metrics; they join the ledger record's single fetch (no part of
the record) and feed the ``moe`` counter group (:func:`count_routing`).
With ``TrainConfig.donate_state`` the step donates its state, so a
caller must not read a step's inputs after it.

Each step is a ``train`` span root (repro.tracing) around the whole
iteration, with the step function's call as its ``train.dispatch``
child; the root's duration is the step time the monitor, the log line
and ``TrainerResult.step_seconds`` take. Recovery is an
``adcc.recover`` span carrying the process's restart ordinal, with an
``adcc.recover.read`` and an ``adcc.recover.verify`` child per slot it
reads and checks.
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import tracing
from ..configs.base import ModelConfig, TrainConfig
from ..core.acc_state import (ChecksumLedger, LedgerRecord,
                              fetch_record_values,
                              verify_state_against_record)
from ..core.slots import (AsyncSlotWriter, SlotStore, flatten_state,
                          unflatten_state)
from ..data.pipeline import SyntheticPipeline
from ..models.registry import build_model, get_config
from ..optim import init_error_state
from ..sharding.partition import make_rules
from .compile_cache import enable_compile_cache
from .mesh import single_device_mesh
from .steps import build_train_step

__all__ = ["ADCCTrainer", "StragglerMonitor", "main"]

# the restart ordinal of each recovery in this process, which its
# adcc.recover span and their children carry
_RESTARTS = itertools.count(1)

# the step's metrics that count routing, fed to the ``moe`` counter group
ROUTING_COUNTS = ("moe_rows", "moe_overflow")


def count_routing(counts, expert_offset: int = 0) -> None:
    """Add a step's routing counts to the ``moe`` counter group: the
    rows routed to each held expert, keyed ``("rows", MoE layer,
    expert)`` (the expert by its number among all the router's), and the
    assignments the dropless buffer could not take, ``("overflow", MoE
    layer)``."""
    group = tracing.counter_group("moe")
    for layer, per_expert in enumerate(np.asarray(counts["moe_rows"])):
        for e, n in enumerate(per_expert):
            group[("rows", layer, expert_offset + e)] += int(n)
    for layer, n in enumerate(np.asarray(counts["moe_overflow"])):
        group[("overflow", layer)] += int(n)


class StragglerMonitor:
    """Step-time outlier detection. At fleet scale each host reports its
    step wall-time; hosts persistently above ``threshold`` x median get
    flagged for hot-spare replacement. Single-host here, interface real."""

    def __init__(self, window: int = 32, threshold: float = 2.0):
        self.window = window
        self.threshold = threshold
        self.times: List[float] = []
        self.flagged_steps: List[int] = []

    def record(self, step: int, seconds: float) -> bool:
        self.times.append(seconds)
        recent = self.times[-self.window:]
        if len(recent) >= 8:
            med = float(np.median(recent))
            if seconds > self.threshold * med:
                self.flagged_steps.append(step)
                return True
        return False


@dataclasses.dataclass
class TrainerResult:
    final_step: int
    losses: List[float]
    resumed_from: Optional[int]
    recovery_report: str
    step_seconds: List[float]


class ADCCTrainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig, workdir: str, *,
                 batch: int = 8, seq: int = 64, mesh=None,
                 slot_every: int = 8, n_slots: int = 3,
                 mode: str = "adcc"):
        """mode: 'adcc' (paper technique) | 'sync' (traditional blocking
        checkpoint baseline) | 'none' (no fault tolerance)."""
        assert mode in ("adcc", "sync", "none")
        self.cfg, self.tcfg = cfg, tcfg
        self.workdir = workdir
        self.batch, self.seq = batch, seq
        self.slot_every, self.mode = slot_every, mode
        os.makedirs(workdir, exist_ok=True)

        self.mesh = mesh if mesh is not None else single_device_mesh()
        self.rules = make_rules(self.mesh, fsdp=tcfg.fsdp)
        self.api = build_model(cfg)
        self.pipeline = SyntheticPipeline(cfg, batch, seq, seed=tcfg.seed)
        sample = {k: jnp.asarray(v)
                  for k, v in self.pipeline.batch_at(0).items()}
        self.step_fn, self.shardings, self.opt_init = build_train_step(
            self.api, tcfg, self.rules, donate=tcfg.donate_state,
            batch_template=sample)
        self.ledger = ChecksumLedger(os.path.join(workdir, "ledger.jsonl"))
        self.store = SlotStore(os.path.join(workdir, "slots"), n_slots)
        self.writer = AsyncSlotWriter(self.store) if mode == "adcc" else None
        self.monitor = StragglerMonitor()
        self._crashed = False

    # -- recovery ---------------------------------------------------------------
    def _try_recover(self):
        """-> (params, opt_state, resume_step, report) or Nones."""
        with tracing.span("adcc.recover", restart=next(_RESTARTS)):
            recs = {r.step: r for r in self.ledger.validated_records()}
            if not recs:
                return None, None, 0, "no ledger"
            template_p, _ = self.api.abstract_init(jax.random.PRNGKey(0))
            for slot, step in self.store.slots_by_recency():
                rec = recs.get(step)
                if rec is None:
                    continue
                with tracing.span("adcc.recover.read", slot=slot):
                    flat = self.store.read_slot(slot)
                if flat is None:
                    continue
                with tracing.span("adcc.recover.verify", slot=slot):
                    try:
                        state = unflatten_state(
                            {"params": template_p,
                             "opt": jax.eval_shape(self.opt_init,
                                                   template_p)}, flat)
                    except (KeyError, ValueError):
                        continue  # torn slot: missing/short leaves
                    ok, bad = verify_state_against_record(
                        state["params"], state["opt"], rec)
                if ok:
                    return (state["params"], state["opt"], step + 1,
                            f"slot {slot} @ step {step} verified")
            newest = max(recs)
            return None, None, 0, (f"no slot verified (ledger reaches step "
                                   f"{newest}); restart from scratch")

    # -- main loop ------------------------------------------------------------------
    def run(self, steps: int, crash_at_step: Optional[int] = None,
            log_every: int = 10) -> TrainerResult:
        params, opt_state, start, report = self._try_recover()
        resumed_from = start - 1 if start > 0 else None
        if params is None:
            params, _ = self.api.init(jax.random.PRNGKey(self.tcfg.seed))
            opt_state = self.opt_init(params)
        else:
            params = jax.tree.map(jnp.asarray, params)
            opt_state = jax.tree.map(jnp.asarray, opt_state)
        err_state = init_error_state(params)

        losses: List[float] = []
        times: List[float] = []
        t = start
        while t < steps:
            with tracing.step("train", t) as root:
                batch = {k: jnp.asarray(v)
                         for k, v in self.pipeline.batch_at(t).items()}
                rng = jax.random.fold_in(jax.random.PRNGKey(self.tcfg.seed), t)
                with tracing.span("train.dispatch"):
                    params, opt_state, err_state, metrics, cks = self.step_fn(
                        params, opt_state, err_state, batch, rng)
                # (3) synchronous tiny ledger write — the "one cache line";
                # 'sync' writes one with each blocking checkpoint
                boundary = (t + 1) % self.slot_every == 0
                counts = {k: v for k, v in metrics.items()
                          if k in ROUTING_COUNTS}
                if self.mode == "adcc" or (self.mode == "sync" and boundary):
                    values = fetch_record_values(metrics["loss"], cks, counts)
                    counts = values.pop("counts", {})
                    rec = LedgerRecord(
                        step=t, rng_seed=self.tcfg.seed,
                        cursor=[self.tcfg.seed, t + 1, 0], **values)
                    self.ledger.append(rec)
                    loss = rec.loss
                elif counts:
                    loss, counts = jax.device_get((metrics["loss"], counts))
                    loss = float(loss)
                else:
                    loss = float(metrics["loss"])
                if counts:
                    count_routing(counts, self.cfg.expert_offset)
                losses.append(loss)

                if self.mode == "adcc" and boundary:
                    # (4) async fence-free heavy-state write
                    self.writer.submit(t, flatten_state(
                        {"params": params, "opt": opt_state}))
                elif self.mode == "sync" and boundary:
                    # traditional checkpoint: blocking full copy
                    self.store.write_slot(
                        self.store.slot_for_step((t + 1) // self.slot_every),
                        t, flatten_state({"params": params, "opt": opt_state}))

            times.append(root.seconds)
            self.monitor.record(t, root.seconds)
            if log_every and t % log_every == 0:
                print(f"step {t:5d} loss {loss:.4f} "
                      f"({root.seconds*1e3:.0f} ms)", flush=True)

            if crash_at_step is not None and t == crash_at_step:
                self.crash()
                return TrainerResult(t, losses, resumed_from, report, times)
            t += 1

        if self.writer is not None:
            self.writer.drain()
        self.ledger.close()
        self._final_params = params  # for tests
        self._final_opt = opt_state
        return TrainerResult(steps - 1, losses, resumed_from, report, times)

    def crash(self) -> None:
        """Simulated node failure: in-flight async writes torn, process
        state dropped. (Real deployment: the job simply dies.)"""
        if self.writer is not None:
            self.writer.crash()
        self.ledger.close()
        self._crashed = True


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="ADCC trainer")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-test-scale config (CPU)")
    ap.add_argument("--mode", default="adcc",
                    choices=["adcc", "sync", "none"])
    ap.add_argument("--slot-every", type=int, default=8)
    ap.add_argument("--crash-at", type=int, default=None)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--remat", default="dots", choices=["none", "dots", "full"])
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8"])
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tcfg = TrainConfig(optimizer=args.optimizer, remat=args.remat,
                       grad_compression=args.grad_compression)
    trainer = ADCCTrainer(cfg, tcfg, args.workdir, batch=args.batch,
                          seq=args.seq, slot_every=args.slot_every,
                          mode=args.mode)
    res = trainer.run(args.steps, crash_at_step=args.crash_at)
    print(f"done: final step {res.final_step}, resumed_from="
          f"{res.resumed_from}, recovery: {res.recovery_report}")
    if res.losses:
        print(f"loss {res.losses[0]:.4f} -> {res.losses[-1]:.4f}")


if __name__ == "__main__":
    main()
