"""``bench/calibrate_donated.py`` on the tiny cut of
nemotron3-nano-30b-a3b, whose step donates its state: the two faults in
the forms a donated step can run, and the calibration's path through
``bench/calibrate.py``."""

import pytest

from bench import training as T
from bench.tests import tiny_nemotron as TN


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench"))
    TN.make_copy(root)
    return root


# the inputs held on the host across the step, and the half-batch step
# built donating
@pytest.mark.parametrize("fault", ["half_batch", "unchanged_state"])
def test_a_broken_donated_step_is_not_correct(root, fault, monkeypatch):
    from bench import calibrate_donated as CD
    build = T.build_trainer

    def broken(*args, **kw):
        trainer = build(*args, **kw)
        assert trainer.tcfg.donate_state
        trainer.step_fn = CD.DONATED_FAULTS[fault](trainer)
        return trainer

    monkeypatch.setattr(T, "build_trainer", broken)
    ctx = TN.context(root, TN.CELL, seconds=0.2)
    outcome = ctx.cell.driver().run(ctx)
    assert not outcome.correct
    assert [k for k, c in outcome.checks.items() if not c.ok], outcome.checks


def test_donated_calibration_reads_every_fault(root, tmp_path, monkeypatch):
    """``bench/calibrate_donated.py``'s path through ``bench/calibrate.py``
    on the tiny cell: one seed with the control and all four faults; the
    program passes its limits, each fault fails one."""
    from bench import calibrate
    from bench import calibrate_donated as CD
    from bench import faults

    monkeypatch.setattr(calibrate, "BENCH_DIR", str(tmp_path))
    monkeypatch.setitem(faults.FAULTS, "unchanged_state",
                        CD.unchanged_state)
    monkeypatch.setitem(faults.FAULTS, "half_batch", CD.half_batch)
    monkeypatch.setattr(T, "build_trainer", CD.build_trainer)
    rows = {}
    calibrate.train_readings(TN.context(root, TN.CELL).cell, [2 ** 31 + 5],
                             1, 1, lambda side, seed, got: rows.update(
                                 {side: got}))
    assert set(rows) == {"program", "control"} | set(faults.FAULTS)
    limits = TN.TINY_LIMITS["train"]
    assert all(rows["program"][k] <= lim for k, lim in limits.items())
    for name in faults.FAULTS:
        assert any(rows[name][k] > lim for k, lim in limits.items()), name
