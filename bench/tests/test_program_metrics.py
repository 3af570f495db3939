"""The per-layer metrics read from the program's own spans
(``repro.tracing``): each reader on a hand-built record, its window, and
nothing read where the record lacks the spans; then the readers on the
record of a traced CPU run of the tiny resume cell."""

import os
import sys

import pytest

from bench.harness import BENCH_DIR, load_module, metric_values
from bench.tests import tiny
from repro import tracing
from repro.tracing import Span

MS = 1_000_000
MAIN, WRITER = 1, 2
TRACE = {"busy_s": 1.0, "window_s": 2.0}
# metric: (span it sums, that span's ms in a window cycle)
READERS = {"recover_read_s": ("adcc.recover.read", 30),
           "recover_verify_s": ("adcc.recover.verify", 14),
           "resume_dispatch_s": ("train.dispatch", 50)}


def reader(name):
    return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py")).read


def cycle(restart: int, t0: int, scale: int = 1) -> list:
    """One restart's spans from ``t0`` ms: the recovery root with two
    slot reads and checks, then the resumed step. The
    warm-up cycle (``scale`` > 1) takes longer at everything."""
    ids = iter(range(restart * 100, restart * 100 + 100))
    root = next(ids)
    attrs = {"restart": restart}

    def mk(name, lo, hi, parent=None, thread=MAIN, **extra):
        a = dict(attrs, **extra) if parent is not None else extra
        return Span(name, (t0 + lo * scale) * MS, (t0 + hi * scale) * MS,
                    parent, thread, a, next(ids))

    step = next(ids)
    return [
        mk("adcc.recover.read", 10, 20, root, slot=1),
        mk("adcc.recover.verify", 20, 24, root),
        mk("adcc.recover.read", 24, 44, root, slot=0),
        mk("adcc.recover.verify", 44, 54, root),
        Span("adcc.recover", t0 * MS, (t0 + 60 * scale) * MS, None, MAIN,
             attrs, root),
        mk("train.dispatch", 70, 120, step, step=8),
        Span("train", (t0 + 65 * scale) * MS, (t0 + 130 * scale) * MS,
             None, MAIN, {"step": 8}, step),
        # the slot writer's thread: no part of a cycle
        mk("train.dispatch", 75, 300, None, thread=WRITER),
    ]


def record(monkeypatch, spans):
    monkeypatch.setattr(tracing, "spans", lambda name=None: [
        s for s in spans if name is None or s.name == name])


@pytest.fixture
def window(monkeypatch):
    """A warm-up cycle (restart 1) and two window cycles (2 and 3)."""
    spans = cycle(1, 0, scale=10) + cycle(2, 2000) + cycle(3, 3000)
    record(monkeypatch, spans)
    return spans


@pytest.mark.parametrize("name", sorted(READERS))
def test_reads_the_window_cycles(window, name):
    _, ms = READERS[name]
    obs = {"restore_s": [0.1, 0.1], "trace": TRACE}
    assert reader(name)(obs) == pytest.approx(ms * 1e-3)
    # one window cycle: the last root alone
    assert reader(name)(dict(obs, restore_s=[0.1])) == pytest.approx(
        ms * 1e-3)
    # three cycles take in the warm-up's ten-times-longer spans
    assert reader(name)(dict(obs, restore_s=[0.1] * 3)) == pytest.approx(
        ms * 1e-3 * 12 / 3)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reads_nothing_outside_a_traced_resume_run(window, name):
    assert reader(name)({"restore_s": [0.1, 0.1], "trace": None}) is None
    assert reader(name)({"restore_s": [0.1, 0.1]}) is None
    assert reader(name)({"restore_s": [], "trace": TRACE}) is None
    assert reader(name)({"step_walls": {5: 0.4}, "trace": TRACE}) is None
    # more cycles than the record holds roots
    assert reader(name)({"restore_s": [0.1] * 4, "trace": TRACE}) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reads_nothing_where_the_spans_are_missing(monkeypatch, name):
    span, _ = READERS[name]
    obs = {"restore_s": [0.1, 0.1], "trace": TRACE}
    record(monkeypatch, [])
    assert reader(name)(obs) is None
    # a window cycle without the span
    spans = cycle(1, 0) + cycle(2, 2000) + [
        s for s in cycle(3, 3000) if s.name != span]
    record(monkeypatch, spans)
    assert reader(name)(obs) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reads_nothing_from_a_program_without_tracing(monkeypatch, name):
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert reader(name)({"restore_s": [0.1], "trace": TRACE}) is None


def test_traced_cpu_resume_run(tmp_path):
    """The tiny resume cell with the profiler on: the readers find the
    program's spans of its window cycles."""
    root = str(tmp_path)
    tiny.make_copy(root)
    ctx = tiny.context(root, "tiny.resume")
    ctx.trace = True
    tracing.reset()
    outcome = ctx.cell.driver().run(ctx)
    assert outcome.correct, outcome.checks
    layers = metric_values(ctx.cell, outcome, trace=True)
    cycles = outcome.observations["restore_s"]
    for name in READERS:
        assert 0 < layers[name]["value"] < max(cycles) + max(
            outcome.observations["resume_step_s"]), name
    roots = sorted(tracing.spans("adcc.recover"),
                   key=lambda s: s.start_ns)[-len(cycles):]
    reads = [s for s in tracing.spans("adcc.recover.read")
             if s.attrs["restart"] == roots[0].attrs["restart"]]
    assert sorted(s.attrs["slot"] for s in reads) == [0, 1]
    # the warm-up cycle and the window's: one recovery each
    assert len(tracing.spans("adcc.recover")) == len(cycles) + 1
