"""Resume cells: restart the ADCC trainer on a crashed workdir, again and
again, timed on the benchmark's clock.

Set-up builds the crash image from the seed through the program's own
writers (``flatten_state``, ``SlotStore.write_slot``,
``ChecksumLedger.append``):

* slot 0: the benchmark's weights and an optimizer state at
  ``verified_step``, complete;
* slot 1: a newer state at ``torn_step``, torn after its first
  ``torn_after_leaves`` leaves;
* a ledger whose records reach ``ledger_to_step``, past both, with the
  per-leaf checksums of the verified state.

A cycle restores the ledger file from set-up's copy, constructs a fresh
``ADCCTrainer`` on the workdir and runs it through its first resumed
step: recovery finds the torn slot, rejects it, verifies slot 0 and
resumes at ``verified_step + 1``, which is no slot boundary, so nothing
is rewritten. Set-up runs one cycle (it compiles or loads the step);
the window runs cycles until ``--seconds`` have passed and closes at the
end of the last one. ``recover_s`` is the mean cycle.

``correct``: in the window's first cycle, the restored parameters and
optimizer state equal the image bit for bit, and the resumed step's
loss and gradient (from the first moment it leaves) agree with the plain
reference's step from the same state and batch; every cycle resumes
from slot 0 and gives the first cycle's loss. The parameter change is
not compared here: from the image's moments Adam's normalization turns
rounding of the gradient's smallest elements into a change of the
smallest leaves' norm as large as the control's (the training cells
compare it from a zero state).
"""

from __future__ import annotations

import gc
import os
import shutil
import statistics
import time
from typing import Any, Dict, List

from bench import training as T
from bench.harness import Outcome

VERIFIED_SLOT = 0
TORN_SLOT = 1


def image_state(cell, seed: int, step: int):
    """The verified slot's state, from the seed (parameters, optimizer)."""
    reference = cell.config_module("reference")
    params = T.make_weights(reference, cell.config, seed)
    return params, T.make_opt_state(params, seed, step + 1)


def checksums(tree) -> List[float]:
    """The ledger's per-leaf checksum: the float32 sum of the leaf."""
    import jax
    import jax.numpy as jnp
    return [float(x) for x in jax.device_get(
        [jnp.sum(leaf.astype(jnp.float32)) for leaf in jax.tree.leaves(tree)])]


def build_image(cell, workdir: str, seed: int) -> bytes:
    """Write the crash image; returns the ledger file's bytes."""
    import jax
    from repro.core.acc_state import ChecksumLedger, LedgerRecord
    from repro.core.slots import SlotStore, flatten_state

    tr = cell.traffic
    shutil.rmtree(workdir, ignore_errors=True)
    params, opt = image_state(cell, seed, tr["verified_step"])
    store = SlotStore(os.path.join(workdir, "slots"), tr["n_slots"])
    store.write_slot(VERIFIED_SLOT, tr["verified_step"],
                     flatten_state({"params": params, "opt": opt}))
    newer = jax.tree.map(lambda x: x * 2 if x.dtype.kind == "f" else x + 8,
                         {"params": params, "opt": opt})
    store.write_slot(TORN_SLOT, tr["torn_step"], flatten_state(newer),
                     tear_after=tr["torn_after_leaves"])
    del newer
    cks_p, cks_o = checksums(params), checksums(opt)
    ledger = ChecksumLedger(os.path.join(workdir, "ledger.jsonl"))
    for t in range(tr["verified_step"], tr["ledger_to_step"] + 1):
        ledger.append(LedgerRecord(
            step=t, rng_seed=seed % 2 ** 31, cursor=[seed % 2 ** 31, t + 1, 0],
            cks_params=cks_p, cks_opt=cks_o, cks_updates=[0.0] * len(cks_p),
            loss=float("nan")))
    ledger.close()
    with open(ledger.path, "rb") as fh:
        return fh.read()


class Held:
    """Keeps one cycle's restored state and step outputs until the cycle
    has been timed."""

    def __init__(self):
        self.inputs = self.out = None

    def __call__(self, i: int, inputs: tuple, out: tuple) -> None:
        self.inputs, self.out = inputs, out


def cycle(cell, workdir: str, seed: int, ledger_bytes: bytes,
          held: Held = None, spans: bool = False, wrap=None) -> Dict[str, Any]:
    """One restart, timed. ``wrap(trainer)``, where given, returns the
    step function to run in place of the trainer's own (a planted
    fault)."""
    import jax

    tr, cfg = cell.traffic, cell.config
    with open(os.path.join(workdir, "ledger.jsonl"), "wb") as fh:
        fh.write(ledger_bytes)
    marks: Dict[str, float] = {}
    span = jax.profiler.TraceAnnotation("bench.restore") if spans else None

    def on_request(t):
        marks.setdefault("request", time.perf_counter())
        if span is not None:
            span.__exit__(None, None, None)
            nxt = jax.profiler.TraceAnnotation("bench.resume_step")
            nxt.__enter__()
            marks["span"] = nxt

    t0 = time.perf_counter()
    if span is not None:
        span.__enter__()
    trainer = T.build_trainer(cfg, workdir, seed, mode="adcc",
                              slot_every=tr["slot_every"],
                              n_slots=tr["n_slots"])
    trainer.pipeline = T.BatchSource(seed, cfg["shape"]["batch"],
                                     cfg["shape"]["seq"],
                                     cfg["model"]["vocab_size"], on_request)
    tap = T.StepTap(wrap(trainer) if wrap else trainer.step_fn,
                    1 if held else 0, held)
    trainer.step_fn = tap
    res = trainer.run(tr["verified_step"] + 2, log_every=0)
    t_end = time.perf_counter()
    if "span" in marks:
        marks["span"].__exit__(None, None, None)
    want = f"slot {VERIFIED_SLOT} @ step {tr['verified_step']} verified"
    out = {"restore_s": marks["request"] - t0,
           "resume_step_s": t_end - marks["request"],
           "ok": res.resumed_from == tr["verified_step"]
           and res.recovery_report == want,
           "report": res.recovery_report,
           "loss": res.losses[0] if res.losses else float("nan")}
    del trainer, tap, res
    return out


def program_readings(cell, seed: int, held: Held) -> tuple:
    """(bit-exact restore gap, the program's readings) of a held cycle."""
    import jax
    import jax.numpy as jnp

    tr = cell.traffic
    params_a, opt_a = image_state(cell, seed, tr["verified_step"])
    (params_in, opt_in), (new_params, new_opt, _, metrics, _) = (
        held.inputs, held.out)
    diffs = jax.tree.map(
        lambda x, y: jnp.max(jnp.abs(jnp.asarray(x, jnp.float32)
                                     - jnp.asarray(y, jnp.float32))),
        (params_in, tuple(opt_in)), (params_a, tuple(opt_a)))
    restore_gap = float(max(jax.device_get(jax.tree.leaves(diffs))))
    b1 = cell.config["train"]["beta1"]
    grads = jax.tree.map(lambda m, m0: (m - b1 * m0) / (1.0 - b1),
                         T.AdamState(*new_opt).m, opt_a.m)
    readings = T.Readings([float(metrics["loss"])], T.leaf_norms(grads),
                          T.diff_norms(new_params, params_a))
    held.inputs = held.out = None
    return restore_gap, readings


def reference_readings(cell, seed: int, operand_dtype=None) -> T.Readings:
    tr, cfg = cell.traffic, cell.config
    params, opt = image_state(cell, seed, tr["verified_step"])
    source = T.BatchSource(seed, cfg["shape"]["batch"], cfg["shape"]["seq"],
                           cfg["model"]["vocab_size"])
    return T.reference_steps(cell.config_module("reference"), cfg, params, opt,
                             [source.tokens(tr["verified_step"] + 1)],
                             operand_dtype=operand_dtype)


def run(ctx) -> Outcome:
    import jax

    cell = ctx.cell
    ledger_bytes = build_image(cell, ctx.workdir, ctx.seed)
    warm = cycle(cell, ctx.workdir, ctx.seed, ledger_bytes)
    gc.collect()
    setup_s = time.perf_counter() - ctx.t_start
    ctx.log(f"set-up {setup_s:.3f} s; warm-up cycle {warm}")

    trace_dir = os.path.join(ctx.workdir, "trace")
    compiles0 = ctx.compiles()
    if ctx.trace:
        jax.profiler.start_trace(trace_dir)
        window_span = jax.profiler.TraceAnnotation("bench.window")
        window_span.__enter__()
    cycles = []
    restore_gap, prog = None, None
    t_open = time.perf_counter()
    while not cycles or time.perf_counter() - t_open < ctx.seconds:
        held = Held() if not cycles else None
        cycles.append(cycle(cell, ctx.workdir, ctx.seed, ledger_bytes, held,
                            spans=ctx.trace))
        if held is not None:
            restore_gap, prog = program_readings(cell, ctx.seed, held)
        gc.collect()
    t_close = time.perf_counter()
    if ctx.trace:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    compiles = ctx.compiles() - compiles0
    peak = T.memory_peak_bytes()
    recover = [c["restore_s"] + c["resume_step_s"] for c in cycles]
    failed = sum(1 for c in cycles
                 if not c["ok"] or c["loss"] != cycles[0]["loss"])
    ctx.log(f"window: {len(cycles)} cycles in {t_close - t_open:.3f} s, "
            f"cycle seconds {[round(r, 4) for r in recover]}, reports "
            f"{sorted({c['report'] for c in cycles})}, compilations in the "
            f"window {compiles}")

    ref = reference_readings(cell, ctx.seed)
    ctx.log(f"losses program {prog.losses} reference {ref.losses}")
    checks = T.checks(cell, "resume",
                      dict(T.compare(prog, ref), restore_gap=restore_gap))

    trace = None
    if ctx.trace:
        from bench import trace as TR
        path = TR.find_xplane(trace_dir)
        trace = TR.reduce(path) if path else None
        shutil.rmtree(trace_dir, ignore_errors=True)
    obs = {"cell": cell.name, "device_kind": ctx.device_kind,
           "restore_s": [c["restore_s"] for c in cycles],
           "resume_step_s": [c["resume_step_s"] for c in cycles],
           "compiles_in_window": compiles}
    return Outcome(end_to_end={"recover_s": statistics.fmean(recover),
                               "setup_s": setup_s},
                   attempted=len(cycles), failed=failed, checks=checks,
                   observations=obs, memory_peak_bytes=peak, trace=trace)
