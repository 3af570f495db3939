"""The program's spans and counters (repro.tracing): nesting per thread,
the counter registry, the record's bound, the profiler's copy of a span,
the launch counter of the batched sweep, and the spans of the ADCC
trainer's steps and recovery; the named scopes of the training step
leave its optimized HLO unchanged."""

import contextlib
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.configs.base import TrainConfig
from repro.core.slots import flatten_state
from repro.launch.train import ADCCTrainer
from repro.models.registry import get_config


@pytest.fixture(autouse=True)
def fresh_record():
    tracing.reset()
    yield
    tracing.reset()


def tiny_trainer(workdir, mode="adcc", slot_every=2):
    cfg = get_config("llama3-8b").reduced()
    tcfg = TrainConfig(remat="none", total_steps=40, warmup_steps=5)
    return ADCCTrainer(cfg, tcfg, workdir, batch=2, seq=16,
                       slot_every=slot_every, mode=mode)


def by_id():
    return {s.id: s for s in tracing.spans()}


# ---------------------------------------------------------------------------
# the record
# ---------------------------------------------------------------------------

def test_spans_nest_per_thread_and_inherit_attributes():
    seen = {}

    def other():
        with tracing.span("worker", slot=3) as w:
            seen["worker"] = w.id

    with tracing.step("root", 7) as root:
        with tracing.span("child", leaves=2) as child:
            with tracing.span("grandchild"):
                pass
            t = threading.Thread(target=other)
            t.start()
            t.join(timeout=10)
        assert not t.is_alive()
    spans = by_id()
    assert spans[root.id].parent is None
    assert spans[root.id].attrs == {"step": 7}
    assert spans[child.id].parent == root.id
    assert spans[child.id].attrs == {"step": 7, "leaves": 2}
    (grand,) = tracing.spans("grandchild")
    assert grand.parent == child.id and grand.attrs == {"step": 7,
                                                        "leaves": 2}
    worker = spans[seen["worker"]]
    assert worker.parent is None and worker.attrs == {"slot": 3}
    assert worker.thread != spans[root.id].thread
    # a parent encloses its child on the clock
    assert root.start_ns <= child.start_ns <= child.end_ns <= root.end_ns
    assert spans[root.id].seconds == pytest.approx(root.seconds)


def test_a_span_ended_by_an_exception_is_recorded():
    with pytest.raises(KeyError):
        with tracing.span("fails"):
            raise KeyError("x")
    with tracing.span("after") as after:
        pass
    assert [s.name for s in tracing.spans()] == ["fails", "after"]
    assert tracing.spans("after")[0].parent is None
    assert after.seconds >= 0


def test_counter_groups_are_named_counters_copied_and_reset_in_place():
    group = tracing.counter_group("probe.group")
    assert tracing.counter_group("probe.group") is group
    group["a"] += 1
    group[("k", "x")] += 2
    got = tracing.counters()
    assert got["probe.group"] == {"a": 1, ("k", "x"): 2}
    assert got["probe.group"]["missing"] == 0
    got["probe.group"]["a"] = 100       # a copy: the counter is unchanged
    assert group["a"] == 1
    with tracing.span("s"):
        pass
    tracing.reset()
    assert tracing.spans() == [] and group == {}
    group["a"] += 1                     # the same object still counts
    assert tracing.counters()["probe.group"]["a"] == 1


def test_the_record_is_bounded():
    for i in range(tracing.MAX_SPANS + 10):
        with tracing.span("s", i=i):
            pass
    spans = tracing.spans()
    assert len(spans) == tracing.MAX_SPANS
    assert spans[0].attrs["i"] == 10        # the oldest went first
    assert spans[-1].attrs["i"] == tracing.MAX_SPANS + 9


def test_a_span_lands_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("tracing.probe", step=3) as probe:
            time.sleep(0.05)
    finally:
        jax.profiler.stop_trace()
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    events = [ev for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events
              if ev.name == "tracing.probe"]
    assert len(events) == 1
    traced = events[0].duration_ns * 1e-9
    assert traced == pytest.approx(probe.seconds, rel=0.05)


def test_batched_launches_count_through_the_registry():
    from repro.core.backends import batched

    assert batched.LAUNCHES is tracing.counter_group("batched.launches")
    before = batched.LAUNCHES.copy()
    batched._count("probe", jnp.zeros(2))
    batched._count("probe", jnp.zeros(2))
    assert batched.LAUNCHES[("probe", "cpu")] - before[("probe", "cpu")] == 2
    assert ("never", "cpu") not in batched.LAUNCHES
    assert batched.LAUNCHES[("never", "cpu")] == 0
    assert tracing.counters()["batched.launches"][("probe", "cpu")] == 2


# ---------------------------------------------------------------------------
# the trainer, its ledger, slots and recovery
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["adcc", "sync", "none"])
def test_trainer_steps_are_span_roots(tmp_path, mode):
    steps = 5
    trainer = tiny_trainer(str(tmp_path), mode=mode, slot_every=2)
    res = trainer.run(steps, log_every=0)
    spans = by_id()
    roots = sorted(tracing.spans("train"), key=lambda s: s.start_ns)
    assert [r.attrs["step"] for r in roots] == list(range(steps))
    assert all(r.parent is None for r in roots)
    # step_seconds and the straggler monitor take the roots' durations
    assert res.step_seconds == [r.seconds for r in roots]
    assert trainer.monitor.times == res.step_seconds
    # one dispatch a step, inside its root
    dispatches = tracing.spans("train.dispatch")
    assert [spans[d.parent] for d in dispatches] == roots
    assert [d.attrs for d in dispatches] == [r.attrs for r in roots]
    assert all(r.start_ns <= d.start_ns and d.end_ns <= r.end_ns
               for d, r in zip(dispatches, roots))
    # a fresh start: one recovery that reads no slot
    (recover,) = tracing.spans("adcc.recover")
    assert recover.end_ns <= roots[0].start_ns
    assert tracing.spans("adcc.recover.read") == []


def test_step_seconds_keep_the_straggler_monitor_working(tmp_path):
    trainer = tiny_trainer(str(tmp_path), mode="none")
    res = trainer.run(10, log_every=0)
    assert len(res.step_seconds) == 10 and all(
        t > 0 for t in res.step_seconds)
    # the monitor flags a step more than its threshold over the median
    med = float(np.median(res.step_seconds[-8:]))
    assert trainer.monitor.record(10, 10 * med + 1.0)
    assert trainer.monitor.flagged_steps[-1] == 10


def test_resume_over_a_torn_slot(tmp_path):
    workdir = str(tmp_path)
    first = tiny_trainer(workdir, slot_every=2)
    first.run(6, log_every=0)            # slots at steps 1, 3, 5; drained
    # slot 2 (step 5) torn as a power loss would leave it: a newer state
    # with only its first leaf written
    newer = jax.tree.map(lambda x: x + 1, {"params": first._final_params,
                                           "opt": first._final_opt})
    first.store.write_slot(2, 5, flatten_state(newer), tear_after=1)
    tracing.reset()

    second = tiny_trainer(workdir, slot_every=2)
    res = second.run(6, log_every=0)
    assert res.resumed_from == 3 and res.recovery_report.startswith("slot 1")
    spans = by_id()
    (root,) = tracing.spans("adcc.recover")
    restart = root.attrs["restart"]
    # one read per slot scanned, newest first; the torn slot is read,
    # checked and rejected, the next one accepted
    reads = tracing.spans("adcc.recover.read")
    checks = tracing.spans("adcc.recover.verify")
    assert [s.attrs["slot"] for s in reads] == [2, 1]
    assert [s.attrs["slot"] for s in checks] == [2, 1]
    assert all(spans[s.parent] == root and s.attrs["restart"] == restart
               for s in reads + checks)
    assert [r.attrs["step"] for r in tracing.spans("train")] == [4, 5]
    # a second restart carries the next ordinal
    third = tiny_trainer(workdir, slot_every=2)
    third.run(4, log_every=0)
    assert [r.attrs["restart"] for r in tracing.spans("adcc.recover")] == [
        restart, restart + 1]


# ---------------------------------------------------------------------------
# the training step's named scopes
# ---------------------------------------------------------------------------

def _step_hlo(scoped: bool, monkeypatch) -> str:
    from repro.launch.mesh import single_device_mesh
    from repro.launch.steps import build_train_step
    from repro.models.registry import build_model
    from repro.optim import init_error_state
    from repro.sharding.partition import make_rules

    cfg = get_config("mamba2-130m").reduced()
    tcfg = TrainConfig()
    api = build_model(cfg)
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
             "labels": jnp.zeros((2, 32), jnp.int32)}
    with monkeypatch.context() as m:
        if not scoped:
            m.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
        fn, _, opt_init = build_train_step(
            api, tcfg, make_rules(single_device_mesh(), fsdp=tcfg.fsdp),
            donate=False, batch_template=batch)
        params, _ = api.abstract_init(jax.random.PRNGKey(0))
        return fn.lower(params, jax.eval_shape(opt_init, params),
                        jax.eval_shape(init_error_state, params), batch,
                        jax.random.PRNGKey(0)).compile().as_text()


def test_named_scopes_leave_the_step_hlo_unchanged(monkeypatch):
    scoped = _step_hlo(True, monkeypatch)
    plain = _step_hlo(False, monkeypatch)
    for scope in ("model/", "optimizer/", "adcc.checksums/"):
        assert scope in scoped and scope not in plain, scope
    # the module's instructions, op for op, without their metadata (and
    # without the stack-frame tables that follow the module)
    strip = lambda t: re.sub(r", metadata=\{[^}]*\}", "",
                             t.split("\nFileNames")[0])
    assert strip(scoped) == strip(plain)
