"""CPU rehearsal of the training driver at a tiny size, called as a
function: the trainer's own ``run`` in the window, the comparison with
the reference, and ``correct`` coming out false when the step under the
timed path is broken."""

import pytest

from bench import training as T
from bench.faults import FAULTS
from bench.harness import metric_values
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench"))
    tiny.make_copy(root)
    return root


@pytest.mark.parametrize("name", ["tiny.ledger", "tiny.slot"])
def test_training_cell_runs_and_is_correct(root, name):
    ctx = tiny.context(root, name)
    outcome = ctx.cell.driver().run(ctx)
    assert outcome.correct, outcome.checks
    assert outcome.attempted > 0 and outcome.failed == 0
    e2e = metric_values(ctx.cell, outcome, trace=False)
    assert set(e2e) == {"train_tokens_per_s", "setup_s"}
    assert e2e["train_tokens_per_s"]["value"] > 0
    layers = metric_values(ctx.cell, outcome, trace=True)
    assert layers["train_mfu"]["value"] > 0
    # no trace on the CPU: the idle share finds nothing and is left out
    assert "idle_pct.train" not in layers
    # the window holds whole slot periods where slots set the pace
    assert outcome.attempted % ctx.cell.traffic["window_align_steps"] == 0
    # slots written in the run are read back and compared
    assert ("slot_leaves_differ" in outcome.checks) == (name == "tiny.slot")


@pytest.mark.parametrize("how", ["altered", "dropped"])
def test_a_wrong_or_missing_slot_is_not_correct(root, how, monkeypatch):
    """The writer is handed a slot with one value altered, or hands
    nothing to its files: the read-back fails."""
    from repro.core.slots import AsyncSlotWriter
    submit = AsyncSlotWriter.submit

    def broken(self, step, flat):
        if how == "dropped":
            return
        flat = dict(flat)
        key = sorted(flat)[-1]
        flat[key] = flat[key].copy()
        flat[key].reshape(-1)[0] += 1
        submit(self, step, flat)

    monkeypatch.setattr(AsyncSlotWriter, "submit", broken)
    ctx = tiny.context(root, "tiny.slot")
    outcome = ctx.cell.driver().run(ctx)
    bad = "slot_leaves_differ" if how == "altered" else "slots_unread"
    assert not outcome.checks[bad].ok, outcome.checks
    assert not outcome.correct


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_step_is_not_correct(root, fault, monkeypatch):
    build = T.build_trainer

    def broken(*args, **kw):
        trainer = build(*args, **kw)
        trainer.step_fn = FAULTS[fault](trainer)
        return trainer

    monkeypatch.setattr(T, "build_trainer", broken)
    ctx = tiny.context(root, "tiny.ledger", seconds=0.2)
    outcome = ctx.cell.driver().run(ctx)
    assert not outcome.correct
    failing = [k for k, c in outcome.checks.items() if not c.ok]
    assert failing, outcome.checks
