"""CPU rehearsal of the resume driver at a tiny size, called as a
function: the crash image, restarts timed on the benchmark's clock, the
bit-exact restore and the resumed step against the reference, and
``correct`` coming out false when the restore or the step is broken."""

import numpy as np
import pytest

from bench import training as T
from bench.faults import FAULTS, RESUME_FAULTS
from bench.harness import metric_values
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench"))
    tiny.make_copy(root)
    return root


def test_resume_cell_runs_and_is_correct(root):
    ctx = tiny.context(root, "tiny.resume")
    outcome = ctx.cell.driver().run(ctx)
    assert outcome.correct, outcome.checks
    assert outcome.checks["restore_gap"].value == 0.0
    assert outcome.attempted >= 1 and outcome.failed == 0
    e2e = metric_values(ctx.cell, outcome, trace=False)
    assert set(e2e) == {"recover_s", "setup_s"}
    layers = metric_values(ctx.cell, outcome, trace=True)
    assert set(layers) == {"restore_s", "resume_step_s"}
    assert (layers["restore_s"]["value"] + layers["resume_step_s"]["value"]
            == pytest.approx(e2e["recover_s"]["value"]))


@pytest.mark.parametrize("fault", RESUME_FAULTS)
def test_a_broken_resumed_step_is_not_correct(root, fault, monkeypatch):
    build = T.build_trainer

    def broken(*args, **kw):
        trainer = build(*args, **kw)
        trainer.step_fn = FAULTS[fault](trainer)
        return trainer

    monkeypatch.setattr(T, "build_trainer", broken)
    ctx = tiny.context(root, "tiny.resume", seconds=0.1)
    outcome = ctx.cell.driver().run(ctx)
    assert not outcome.correct


def test_an_altered_restore_is_not_correct(root, monkeypatch):
    """A restored value off by less than the ledger's checksum tolerance:
    recovery accepts the slot, the bit-exact comparison does not."""
    from repro.core.slots import SlotStore
    read = SlotStore.read_slot

    def altered(self, k):
        flat = read(self, k)
        if flat and "params/norm_f" in flat:
            flat["params/norm_f"] = flat["params/norm_f"].copy()
            flat["params/norm_f"][0] += np.float32(1e-3)
        return flat

    monkeypatch.setattr(SlotStore, "read_slot", altered)
    ctx = tiny.context(root, "tiny.resume", seconds=0.1)
    outcome = ctx.cell.driver().run(ctx)
    assert outcome.failed == 0
    assert outcome.checks["restore_gap"].value > 0
    assert not outcome.correct
