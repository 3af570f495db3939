"""Faults planted under the timed path, to show that ``correct`` fails on
them. Each takes the trainer and returns the step function to run in its
place. The benchmark's own runs never use them; ``bench/calibrate.py``
reads them on the chip and the tests in ``bench/tests`` on the CPU."""

from __future__ import annotations


def unchanged_state(trainer):
    """A step that returns its state unchanged."""
    step = trainer.step_fn

    def fault(params, opt, err, batch, rng):
        out = step(params, opt, err, batch, rng)
        return (params, opt, err) + tuple(out[3:])
    return fault


def half_batch(trainer):
    """Half of each batch left out, the mean taken over the rest: a step
    built by the program's ``build_train_step`` for half the rows."""
    from repro.launch.steps import build_train_step

    half = trainer.batch // 2
    built = {}

    def fault(params, opt, err, batch, rng):
        part = {k: v[:half] for k, v in batch.items()}
        if "step" not in built:
            built["step"], _, _ = build_train_step(
                trainer.api, trainer.tcfg, trainer.rules, donate=False,
                batch_template=part)
        return built["step"](params, opt, err, part, rng)
    return fault


def altered_loss(trainer, factor: float = 1.01):
    """The answer altered where it is produced: the step's loss."""
    step = trainer.step_fn

    def fault(params, opt, err, batch, rng):
        out = step(params, opt, err, batch, rng)
        metrics = dict(out[3], loss=out[3]["loss"] * factor)
        return tuple(out[:3]) + (metrics,) + tuple(out[4:])
    return fault


def altered_checksum(trainer, factor: float = 1.001):
    """The answer altered where it is produced: the parameter checksums
    the step hands to the ledger."""
    import jax
    step = trainer.step_fn

    def fault(params, opt, err, batch, rng):
        out = step(params, opt, err, batch, rng)
        cks = dict(out[4], params=jax.tree.map(lambda c: c * factor,
                                               out[4]["params"]))
        return tuple(out[:4]) + (cks,)
    return fault


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "altered_loss": altered_loss, "altered_checksum": altered_checksum}
# the resume cell compares no ledger record
RESUME_FAULTS = ("unchanged_state", "half_batch", "altered_loss")
