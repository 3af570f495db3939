#!/usr/bin/env python3
"""``bench/calibrate.py`` for a training cell whose step donates its state
(``train.donate_state``), at the cell's own size:

    python3 bench/calibrate_donated.py --workload nemotron3-nano-30b-a3b.ledger --seeds 12 --control 3 --faults 3

Options, readings and summary are ``bench/calibrate.py``'s. A donated
step consumes its inputs, and a chip that holds the state once has no
room for a second copy, so two faults of ``bench/faults.py`` run here in
forms that keep one copy of the state on the device:

* ``unchanged_state``: the step's inputs are copied to the host before
  the step and put back on the device in place of its new state;
* ``half_batch``: the half-batch step is built donating, as the cell's
  own step is.

``altered_loss`` and ``altered_checksum`` run as they are. Between seeds
the trainer's final state is dropped, before the next seed's weights
are made. The benchmark's own runs never run this.
"""

from __future__ import annotations

import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH_DIR]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import calibrate, faults  # noqa: E402
from bench import training as T  # noqa: E402


def unchanged_state(trainer):
    """A step that returns its state unchanged: its inputs, held on the
    host across the step, put back where they were."""
    import jax
    step = trainer.step_fn

    def fault(params, opt, err, batch, rng):
        where = jax.tree.map(lambda a: a.sharding, (params, opt, err))
        kept = jax.device_get((params, opt, err))
        # the step's new state is let go here, before the kept one is put
        # back
        rest = tuple(step(params, opt, err, batch, rng)[3:])
        return tuple(jax.device_put(kept, where)) + rest
    return fault


def half_batch(trainer):
    """``bench.faults.half_batch`` with the half-batch step donating as
    the trainer's own does."""
    from repro.launch.steps import build_train_step

    half = trainer.batch // 2
    built = {}

    def fault(params, opt, err, batch, rng):
        part = {k: v[:half] for k, v in batch.items()}
        if "step" not in built:
            built["step"], _, _ = build_train_step(
                trainer.api, trainer.tcfg, trainer.rules,
                donate=trainer.tcfg.donate_state, batch_template=part)
        return built["step"](params, opt, err, part, rng)
    return fault


DONATED_FAULTS = {"unchanged_state": unchanged_state,
                  "half_batch": half_batch}


def build_trainer(*args, **kw):
    """``bench.training.build_trainer``, with a ``run`` that lets go of
    the final state it keeps."""
    trainer = _BUILD_TRAINER(*args, **kw)
    run = trainer.run

    def run_and_drop(*a, **k):
        out = run(*a, **k)
        trainer._final_params = trainer._final_opt = None
        return out
    trainer.run = run_and_drop
    return trainer


_BUILD_TRAINER = T.build_trainer


def main(argv=None) -> int:
    faults.FAULTS.update(DONATED_FAULTS)
    T.build_trainer = build_trainer
    return calibrate.main(argv)


if __name__ == "__main__":
    sys.exit(main())
