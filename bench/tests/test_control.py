"""The control of ``correct``: the plain reference put in the program's
place and computed with the operands of its matrix products in float8
(the precision below the bfloat16 the configuration computes in) comes
out not correct under the committed limits
(``configs/mamba2-130m/limits.json``), through the same checks and
``Outcome.correct`` as a run, on three seeds. The size is the published
widths with 2 of the 24 layers and a batch of 2 x 256, which a test run
can hold; ``bench/calibrate.py`` reads the control at the cell's size on
the chip."""

import jax.numpy as jnp
import pytest

from bench import training as T
from bench.drivers import resume as R
from bench.drivers import train as D
from bench.harness import Outcome
from bench.tests import tiny

SEEDS = [2 ** 31 + 99, 7, 123_456_789_012]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench"))
    tiny.make_copy(root)
    return root


def outcome(checks) -> Outcome:
    return Outcome(end_to_end={}, attempted=1, failed=0, checks=checks,
                   observations={}, memory_peak_bytes=None)


@pytest.mark.parametrize("seed", SEEDS)
def test_float8_control_is_not_correct_in_training(root, seed):
    cell = tiny.context(root, "slim.ledger").cell
    cfg = cell.config
    source = T.BatchSource(seed, cfg["shape"]["batch"], cfg["shape"]["seq"],
                           cfg["model"]["vocab_size"])
    ref = D.reference_readings(cell, seed, source)
    ctl = D.reference_readings(cell, seed, source,
                               operand_dtype=jnp.dtype(cfg["control_dtype"]))
    same = outcome(T.checks(cell, "train", T.compare(ref, ref)))
    assert same.correct, same.checks
    got = outcome(T.checks(cell, "train", T.compare(ctl, ref)))
    assert not got.correct, got.checks


@pytest.mark.parametrize("seed", SEEDS)
def test_float8_control_is_not_correct_on_resume(root, seed):
    cell = tiny.context(root, "slim.resume").cell
    ref = R.reference_readings(cell, seed)
    ctl = R.reference_readings(
        cell, seed, operand_dtype=jnp.dtype(cell.config["control_dtype"]))
    # the reference restores the image as it is
    got = outcome(T.checks(cell, "resume",
                           dict(T.compare(ctl, ref), restore_gap=0.0)))
    assert not got.correct, got.checks
