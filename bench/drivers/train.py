"""Training cells: ``ADCCTrainer.run`` on the benchmark's batches and
weights, timed on the benchmark's clock.

One trainer is built per run, in the mode, slot interval and slot count
that the traffic file gives. The benchmark puts its batch source in the
trainer's ``pipeline``, its weights behind the model's ``init``, and a
tap on the step function, then calls ``run`` once:

* the first ``warmup_steps`` steps are set-up (the first compiles or
  loads the step from the cache; the tap reduces steps 0-2 for the
  comparison with the reference);
* the window opens at the request for batch ``warmup_steps`` and closes
  at the first request at least ``--seconds`` later that lies a multiple
  of ``window_align_steps`` steps on (whole slot periods, where slots
  set the pace). Every ledger append, state flatten and blocked slot
  submit between those requests is charged to the window.

The request after the window raises in the batch source, which ends
``run``; queued slot writes are then dropped as a crash would drop them.

``correct``: the program's losses of steps 0-2, its first gradient (from
the optimizer's first moment after step 0), its parameter change over
the three steps and the parameter checksums its ledger recorded for
them, against the plain float32 reference trained from the same weights
on the same batches; each number under its limit from the
configuration's ``limits.json``. Where the run hands slots to the
writer, every slot it completed is read back from its files and has to
equal, bit for bit, the state it was handed (digests taken on the device
at the submitting step), and at least one has to be complete.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import time
from typing import Any, Dict

import numpy as np

from bench import training as T
from bench.harness import Check, Outcome

COMPARED_STEPS = 3


class FirstSteps:
    """Reduces the tapped first steps to the program's readings. The
    parameters after the last compared step go to the host, and their
    change is taken once the window has closed, from the seed's
    weights."""

    def __init__(self, beta1: float):
        self.beta1 = beta1
        self.losses = []
        self.grad_norms = None
        self.params_after = None

    def __call__(self, i: int, inputs: tuple, out: tuple) -> None:
        import jax
        new_params, new_opt, _, metrics, _ = out
        self.losses.append(metrics["loss"])
        if i == 0:
            m = T.AdamState(*new_opt).m
            self.grad_norms = T.leaf_norms(m) / (1.0 - self.beta1)
        if i == COMPARED_STEPS - 1:
            self.params_after = jax.device_get(new_params)

    def readings(self, params0, ledger_path: str) -> T.Readings:
        import jax.numpy as jnp
        import jax
        after = jax.tree.map(jnp.asarray, self.params_after)
        return T.Readings([float(x) for x in self.losses], self.grad_norms,
                          T.diff_norms(after, params0),
                          ledger_param_sums(ledger_path))


def ledger_param_sums(path: str) -> np.ndarray:
    """The per-leaf parameter checksums the ledger file holds for the
    compared steps (steps x leaves; a step it lacks reads NaN)."""
    by_step = {}
    with open(path) as fh:
        for line in fh:
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                break
            if rec["step"] < COMPARED_STEPS:
                by_step[rec["step"]] = rec["cks_params"]
    width = max((len(v) for v in by_step.values()), default=0)
    return np.asarray([by_step.get(t, [float("nan")] * width)
                       for t in range(COMPARED_STEPS)], np.float64)


def first_steps(config, trainer, params, source, clock=None,
                step_fn=None) -> tuple:
    """Install the benchmark's weights, batches and tap on ``trainer``."""
    T.check_layout(trainer, params)
    T.give_weights(trainer, params)
    trainer.pipeline = source
    first = FirstSteps(config["train"]["beta1"])
    tap = T.StepTap(step_fn or trainer.step_fn, COMPARED_STEPS, first, clock)
    trainer.step_fn = tap
    return first, tap


class SlotWatch:
    """Digests of what the trainer hands its slot writer: the new
    parameters and optimizer state of each step that submits a slot."""

    def __init__(self, slot_every: int):
        self.slot_every = slot_every
        self.handed: Dict[int, Dict[str, tuple]] = {}

    def __call__(self, t: int, out: tuple) -> None:
        if (t + 1) % self.slot_every == 0:
            self.handed[t] = T.state_digest({"params": out[0],
                                             "opt": out[1]})


def slot_gaps(slots_dir: str, handed: Dict[int, Dict[str, tuple]]) -> tuple:
    """(slots read back, leaves that differ from what was handed) over
    every slot that its ``meta.json`` marks complete, read straight from
    its ``.npy`` files. A leaf that is missing or unreadable differs."""
    read, differ = 0, 0
    for name in sorted(os.listdir(slots_dir)) if os.path.isdir(
            slots_dir) else ():
        d = os.path.join(slots_dir, name)
        try:
            with open(os.path.join(d, "meta.json")) as fh:
                meta = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        if not meta.get("complete") or meta.get("step") not in handed:
            continue
        for key, words in handed[meta["step"]].items():
            try:
                got = T.array_digest(np.load(os.path.join(
                    d, key.replace("/", "__") + ".npy")))
            except (OSError, ValueError):
                got = None
            differ += got != words
        read += 1
    return read, differ


def reference_readings(cell, seed: int, source, operand_dtype=None
                       ) -> T.Readings:
    reference = cell.config_module("reference")
    params = T.make_weights(reference, cell.config, seed)
    batches = [source.tokens(t) for t in range(COMPARED_STEPS)]
    return T.reference_steps(reference, cell.config, params,
                             T.zero_state(params), batches,
                             operand_dtype=operand_dtype)


def run(ctx) -> Outcome:
    cell, traffic = ctx.cell, ctx.cell.traffic
    cfg = cell.config
    shutil.rmtree(ctx.workdir, ignore_errors=True)
    trainer = T.build_trainer(cfg, ctx.workdir, ctx.seed,
                              mode=traffic["mode"],
                              slot_every=traffic["slot_every"],
                              n_slots=traffic["n_slots"])
    reference = cell.config_module("reference")
    params = T.make_weights(reference, cfg, ctx.seed)

    trace_dir = os.path.join(ctx.workdir, "trace")
    state: Dict[str, Any] = {}

    def on_open():
        state["setup_s"] = time.perf_counter() - ctx.t_start
        state["compiles0"] = ctx.compiles()
        if ctx.trace:
            import jax
            jax.profiler.start_trace(trace_dir)
            state["window_span"] = jax.profiler.TraceAnnotation(
                "bench.window")
            state["window_span"].__enter__()

    def on_close():
        state["compiles"] = ctx.compiles() - state["compiles0"]
        if ctx.trace:
            import jax
            clock.end_span()
            jax.block_until_ready(tap.losses[-1])
            state["window_span"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    warmup = traffic["warmup_steps"]
    clock = T.Clock(warmup, ctx.seconds, traffic.get("window_align_steps", 1),
                    on_open, on_close, spans=ctx.trace)
    source = T.BatchSource(ctx.seed, cfg["shape"]["batch"],
                           cfg["shape"]["seq"], cfg["model"]["vocab_size"],
                           on_request=clock.request)
    first, tap = first_steps(cfg, trainer, params, source, clock)
    watch = SlotWatch(traffic["slot_every"])
    if traffic["mode"] != "none":      # mode none writes no slot
        tap.each = watch
    del params
    try:
        trainer.run(10 ** 9, log_every=0)
        raise RuntimeError("the trainer stopped before the window closed")
    except T.WindowClosed:
        pass
    if trainer.writer is not None:
        trainer.writer.crash()
        # the write in flight stops at its next leaf
        trainer.writer.drain()
    trainer.ledger.close()
    peak = T.memory_peak_bytes()
    window_losses = np.asarray([float(x) for x in
                                tap.losses[warmup:clock.close_step]])
    failed = int(np.sum(~np.isfinite(window_losses)))
    walls = clock.step_walls()
    del trainer, tap
    gc.collect()
    prog = first.readings(T.make_weights(reference, cfg, ctx.seed),
                          os.path.join(ctx.workdir, "ledger.jsonl"))
    del first
    ctx.log(f"window: {clock.steps} steps in {clock.window_s:.3f} s, "
            f"set-up {state['setup_s']:.3f} s, compilations in the window "
            f"{state['compiles']}")
    ctx.log(f"step walls (s): {[round(w, 4) for w in walls.values()]}")

    ref = reference_readings(cell, ctx.seed, source)
    ctx.log(f"losses program {prog.losses} reference {ref.losses}")
    checks = T.checks(cell, "train", T.compare(prog, ref))
    if watch.handed:
        # every slot the writer completed holds what it was handed, bit
        # for bit, and the run completed at least one
        n_read, differ = slot_gaps(os.path.join(ctx.workdir, "slots"),
                                   watch.handed)
        ctx.log(f"slots handed at steps {sorted(watch.handed)}, "
                f"{n_read} read back complete")
        checks["slot_leaves_differ"] = Check(differ, 0)
        checks["slots_unread"] = Check(int(n_read == 0), 0)

    tokens = clock.steps * cfg["shape"]["batch"] * cfg["shape"]["seq"]
    slot_every = traffic["slot_every"]
    obs = {
        "config": cfg, "cell": cell.name, "device_kind": ctx.device_kind,
        "flops_per_token": cell.config_module("flops").train_flops_per_token(
            cfg),
        "tokens_per_s": tokens / clock.window_s,
        "step_walls": walls,
        "slot_steps": [t for t in walls if (t + 1) % slot_every == 0],
        "compiles_in_window": state["compiles"],
    }
    trace = None
    if ctx.trace:
        from bench import trace as TR
        path = TR.find_xplane(trace_dir)
        trace = TR.reduce(path) if path else None
        shutil.rmtree(trace_dir, ignore_errors=True)
    return Outcome(
        end_to_end={"train_tokens_per_s": tokens / clock.window_s,
                    "setup_s": state["setup_s"]},
        attempted=clock.steps, failed=failed, checks=checks,
        observations=obs, memory_peak_bytes=peak, trace=trace)
