"""ADCC training-state integration tests: ledger invariants, torn-slot
rejection, crash/restart bitwise recovery, elastic restore, optimizer and
compression substrates."""

import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import TrainConfig
from repro.core.acc_state import (ChecksumLedger, LedgerRecord,
                                  fetch_record_values,
                                  verify_state_against_record)
from repro.core.slots import SlotStore, flatten_state, unflatten_state
from repro.launch.train import ADCCTrainer, StragglerMonitor
from repro.models.registry import get_config


def tiny_trainer(workdir, mode="adcc", slot_every=6, optimizer="adamw"):
    cfg = get_config("llama3-8b").reduced()
    tcfg = TrainConfig(remat="none", total_steps=40, warmup_steps=5,
                       optimizer=optimizer)
    return ADCCTrainer(cfg, tcfg, workdir, batch=4, seq=32,
                       slot_every=slot_every, mode=mode)


class TestLedger:
    def test_append_and_read(self, tmp_path):
        led = ChecksumLedger(str(tmp_path / "l.jsonl"))
        for t in range(3):
            led.append(LedgerRecord(step=t, rng_seed=0, cursor=[0, t + 1, 0],
                                    cks_params=[1.0 * t], cks_opt=[2.0 * t],
                                    cks_updates=[1.0 if t else 0.0],
                                    loss=1.0))
        led.close()
        assert len(led.read_all()) == 3

    def test_torn_tail_line_discarded(self, tmp_path):
        path = str(tmp_path / "l.jsonl")
        led = ChecksumLedger(path)
        led.append(LedgerRecord(0, 0, [0, 1, 0], [1.0], [0.0], [0.0], 1.0))
        led.close()
        with open(path, "a") as fh:
            fh.write('{"step": 1, "rng_seed": 0, "cursor": [0,2,0], "cks_p')
        assert len(ChecksumLedger(path).read_all()) == 1

    def test_linearity_chain_breaks_on_corruption(self, tmp_path):
        led = ChecksumLedger(str(tmp_path / "l.jsonl"))
        cks = 10.0
        for t in range(5):
            upd = 0.5
            cks_rec = cks + upd if t != 3 else cks + 99.0  # corrupt step 3
            led.append(LedgerRecord(t, 0, [0, t + 1, 0], [cks_rec], [0.0],
                                    [upd], 1.0))
            cks = cks + upd
        led.close()
        good = led.validated_records()
        assert [r.step for r in good] == [0, 1, 2]

    def test_verify_state_against_record(self):
        params = {"w": jnp.ones((4, 4))}
        opt = {"m": jnp.zeros((4, 4))}
        rec = LedgerRecord(0, 0, [0, 1, 0], [16.0], [0.0], [0.0], 1.0)
        ok, bad = verify_state_against_record(params, opt, rec)
        assert ok and bad == 0
        rec_bad = LedgerRecord(0, 0, [0, 1, 0], [17.0], [0.0], [0.0], 1.0)
        ok, bad = verify_state_against_record(params, opt, rec_bad)
        assert not ok and bad == 1


class TestSlots:
    def _state(self, seed=0):
        k = jax.random.PRNGKey(seed)
        return {"params": {"a": jax.random.normal(k, (8, 8)),
                           "b": jnp.arange(4.0) + seed}}

    def test_roundtrip(self, tmp_path):
        store = SlotStore(str(tmp_path), n_slots=2)
        state = self._state()
        store.write_slot(0, 5, flatten_state(state))
        flat = store.read_slot(0)
        rebuilt = unflatten_state(state, flat)
        assert np.allclose(rebuilt["params"]["a"], state["params"]["a"])

    def test_torn_write_detectable(self, tmp_path):
        store = SlotStore(str(tmp_path), n_slots=2)
        s1 = self._state(seed=1)
        store.write_slot(0, 5, flatten_state(s1))
        s2 = self._state(seed=2)
        store.write_slot(0, 9, flatten_state(s2), tear_after=1)  # torn!
        flat = store.read_slot(0)
        rebuilt = unflatten_state(s1, flat)
        # mixed generations: checksum verification must reject
        sums = [float(jnp.sum(x)) for x in jax.tree.leaves(rebuilt)]
        want = [float(jnp.sum(x)) for x in jax.tree.leaves(s2)]
        assert not np.allclose(sums, want)

    def test_recency_order(self, tmp_path):
        store = SlotStore(str(tmp_path), n_slots=3)
        for k, step in [(0, 3), (1, 7), (2, 5)]:
            store.write_slot(k, step, flatten_state(self._state(step)))
        assert store.slots_by_recency() == [(1, 7), (2, 5), (0, 3)]


class TestCrashRestart:
    def test_bitwise_recovery(self, tmp_path):
        ref_dir, crash_dir = str(tmp_path / "ref"), str(tmp_path / "crash")
        ref = tiny_trainer(ref_dir)
        r_ref = ref.run(24, log_every=0)

        tr1 = tiny_trainer(crash_dir)
        tr1.run(24, crash_at_step=15, log_every=0)
        tr2 = tiny_trainer(crash_dir)
        r2 = tr2.run(24, log_every=0)
        assert r2.resumed_from is not None and r2.resumed_from >= 5
        diffs = jax.tree.map(
            lambda a, b: float(jnp.max(jnp.abs(
                a.astype(jnp.float32) - b.astype(jnp.float32)))),
            ref._final_params, tr2._final_params)
        assert max(jax.tree.leaves(diffs)) == 0.0

    def test_recovery_skips_torn_slot(self, tmp_path):
        wd = str(tmp_path / "t")
        tr1 = tiny_trainer(wd, slot_every=4)
        tr1.run(20, crash_at_step=18, log_every=0)
        # corrupt the newest slot's first tensor (simulate torn write)
        store = tr1.store
        newest_slot, newest_step = store.slots_by_recency()[0]
        d = store.slot_dir(newest_slot)
        fn = sorted(f for f in os.listdir(d) if f.endswith(".npy"))[0]
        arr = np.load(os.path.join(d, fn))
        arr = arr + 1000.0
        np.save(os.path.join(d, fn), arr)

        tr2 = tiny_trainer(wd, slot_every=4)
        r2 = tr2.run(20, log_every=0)
        # must have recovered from an OLDER slot than the corrupted one
        assert r2.resumed_from is not None
        assert r2.resumed_from < newest_step

    def test_sync_mode_also_recovers(self, tmp_path):
        wd = str(tmp_path / "s")
        tr1 = tiny_trainer(wd, mode="sync", slot_every=4)
        tr1.run(16, crash_at_step=12, log_every=0)
        tr2 = tiny_trainer(wd, mode="sync", slot_every=4)
        r2 = tr2.run(16, log_every=0)
        assert r2.resumed_from is not None


class _Tap:
    """Keeps the loss and checksums that each step hands the trainer."""

    def __init__(self, fn):
        self.fn, self.outs = fn, []

    def __call__(self, *args):
        out = self.fn(*args)
        self.outs.append((out[3]["loss"], out[4]))
        return out


def _per_leaf_line(t, seed, loss, cks):
    """A record's line as one blocking ``float`` per scalar makes it."""
    return LedgerRecord(
        step=t, rng_seed=seed, cursor=[seed, t + 1, 0],
        cks_params=[float(x) for x in jax.tree.leaves(cks["params"])],
        cks_opt=[float(x) for x in jax.tree.leaves(cks["opt"])],
        cks_updates=[float(x) for x in jax.tree.leaves(cks["updates"])],
        loss=float(loss)).to_json() + "\n"


class TestLedgerFetch:
    """A ledger record's loss and checksums reach the host in one
    transfer, with the values one ``float`` per scalar gives."""

    STEPS, SLOT_EVERY = 4, 2
    _runs = {}

    @pytest.fixture
    def counted_run(self, tmp_path_factory, monkeypatch):
        """(trainer, tap, fetches, scalar pulls) of a short run in a mode;
        the step function is compiled once for every mode."""
        def run(mode):
            if mode in self._runs:
                return self._runs[mode]
            tr = tiny_trainer(str(tmp_path_factory.mktemp(mode)), mode=mode,
                              slot_every=self.SLOT_EVERY)
            if self._runs:
                tr.step_fn = next(iter(self._runs.values()))[1].fn
            tap = tr.step_fn = _Tap(tr.step_fn)
            calls = {"fetch": 0, "float": 0}
            array_type = type(jnp.zeros(()))
            device_get, array_float = jax.device_get, array_type.__float__

            def counted_get(x):
                calls["fetch"] += 1
                return device_get(x)

            def counted_float(x):
                calls["float"] += 1
                return array_float(x)
            with monkeypatch.context() as m:
                m.setattr(jax, "device_get", counted_get)
                m.setattr(array_type, "__float__", counted_float)
                tr.run(self.STEPS, log_every=0)
            self._runs[mode] = (tr, tap, calls["fetch"], calls["float"])
            return self._runs[mode]
        return run

    @pytest.mark.parametrize("mode", ["adcc", "sync"])
    def test_ledger_bytes_match_per_leaf_pulls(self, counted_run, mode):
        tr, tap, _, _ = counted_run(mode)
        recorded = [t for t in range(self.STEPS)
                    if mode == "adcc" or (t + 1) % self.SLOT_EVERY == 0]
        want = "".join(_per_leaf_line(t, tr.tcfg.seed, *tap.outs[t])
                       for t in recorded)
        with open(tr.ledger.path) as fh:
            assert fh.read() == want

    @pytest.mark.parametrize("mode", ["adcc", "sync"])
    def test_recovery_verifies_against_fetched_records(self, counted_run,
                                                       mode):
        tr, _, _, _ = counted_run(mode)
        again = tiny_trainer(tr.workdir, mode=mode,
                             slot_every=self.SLOT_EVERY)
        params, _, start, report = again._try_recover()
        # the newest slot whose step the validated ledger holds
        valid = {r.step for r in again.ledger.validated_records()}
        newest = max(step for _, step in again.store.slots_by_recency()
                     if step in valid)
        assert params is not None and start == newest + 1, report
        assert report.endswith(f"@ step {newest} verified")
        if mode == "adcc":
            assert newest == self.STEPS - 1

    @pytest.mark.parametrize("mode, fetches, pulls", [
        ("adcc", STEPS, 0),
        ("sync", STEPS // SLOT_EVERY, STEPS - STEPS // SLOT_EVERY),
        ("none", 0, STEPS)])
    def test_one_fetch_per_record(self, counted_run, mode, fetches, pulls):
        _, _, got_fetches, got_pulls = counted_run(mode)
        assert (got_fetches, got_pulls) == (fetches, pulls)

    def test_wrapped_step_checksums_reach_the_ledger(self, tmp_path):
        """A step function wrapped to scale its parameter checksums, as
        the benchmark's altered-checksum fault does, is what the ledger
        records."""
        tr = tiny_trainer(str(tmp_path / "w"))
        tap = _Tap(tr.step_fn)

        def scaled(*args):
            out = tap(*args)
            cks = dict(out[4], params=jax.tree.map(lambda c: c * 2.0,
                                                   out[4]["params"]))
            return tuple(out[:4]) + (cks,)
        tr.step_fn = scaled
        tr.run(2, log_every=0)
        recs = tr.ledger.read_all()
        assert [r.step for r in recs] == [0, 1]
        for rec, (loss, cks) in zip(recs, tap.outs):
            assert rec.cks_params == [2.0 * float(x)
                                      for x in jax.tree.leaves(cks["params"])]
            assert rec.cks_opt == [float(x)
                                   for x in jax.tree.leaves(cks["opt"])]
            assert rec.loss == float(loss)

    def test_fetch_record_values_splits_in_leaf_order(self):
        cks = {"params": {"b": jnp.float32(2.5), "a": jnp.float32(-1e-8)},
               "opt": (jnp.float32(3.0),),
               "updates": {"b": jnp.float32(0.1), "a": jnp.float32(7.0)}}
        got = fetch_record_values(jnp.float32(0.3), cks)
        assert got == {"loss": float(jnp.float32(0.3)),
                       "cks_params": [float(jnp.float32(-1e-8)), 2.5],
                       "cks_opt": [3.0],
                       "cks_updates": [7.0, float(jnp.float32(0.1))]}


class TestElasticCheckpoint:
    def test_save_restore_roundtrip(self, tmp_path):
        from repro.checkpoint.manager import (restore_checkpoint,
                                              save_checkpoint)
        state = {"w": jnp.ones((8, 16)), "step": jnp.int32(7)}
        save_checkpoint(str(tmp_path / "ck"), state, step=7)
        restored, meta = restore_checkpoint(str(tmp_path / "ck"), state)
        assert meta["step"] == 7
        assert np.allclose(restored["w"], 1.0)

    def test_elastic_restore_new_mesh(self, tmp_path):
        from repro.checkpoint.manager import restore_elastic, save_checkpoint
        from repro.launch.mesh import single_device_mesh
        from repro.sharding.partition import make_rules
        state = {"w": jnp.ones((8, 16))}
        axes = {"w": ("embed", "mlp")}
        save_checkpoint(str(tmp_path / "ck"), state, step=3)
        mesh = single_device_mesh()
        rules = make_rules(mesh, fsdp=True)
        placed, meta = restore_elastic(str(tmp_path / "ck"), state, rules,
                                       axes)
        assert np.allclose(np.asarray(placed["w"]), 1.0)


class TestOptim:
    def test_adafactor_trains(self, tmp_path):
        tr = tiny_trainer(str(tmp_path / "af"), optimizer="adafactor")
        res = tr.run(12, log_every=0)
        assert np.isfinite(res.losses).all()

    def test_adafactor_3d_params(self):
        """Regression: factored stats broadcasting for stacked (L, D, F)
        params (the kimi-k2 train_4k failure)."""
        from repro.optim.adamw import adafactor_init, adafactor_update
        tcfg = TrainConfig(optimizer="adafactor")
        params = {"w": jnp.ones((6, 16, 8))}
        grads = {"w": jnp.full((6, 16, 8), 0.1)}
        state = adafactor_init(params)
        upd, state = adafactor_update(tcfg, grads, state, params)
        assert upd["w"].shape == (6, 16, 8)
        assert bool(jnp.all(jnp.isfinite(upd["w"])))

    def test_int8_compression_error_feedback(self):
        from repro.optim.compression import (compress_decompress,
                                             init_error_state)
        key = jax.random.PRNGKey(0)
        g = {"w": jax.random.normal(key, (64, 64))}
        err = init_error_state(g)
        # accumulate compressed grads over many rounds: with error
        # feedback the *mean* compressed signal converges to the truth
        total_c = jnp.zeros((64, 64))
        for i in range(64):
            gc, err = compress_decompress(g, err, jax.random.fold_in(key, i))
            total_c = total_c + gc["w"]
        rel = float(jnp.linalg.norm(total_c / 64 - g["w"])
                    / jnp.linalg.norm(g["w"]))
        assert rel < 0.02, rel


class TestStraggler:
    def test_flags_outliers(self):
        mon = StragglerMonitor(window=16, threshold=2.0)
        for t in range(20):
            flagged = mon.record(t, 1.0 if t != 15 else 5.0)
            if t == 15:
                assert flagged
        assert mon.flagged_steps == [15]

    def test_no_false_positives_on_uniform(self):
        mon = StragglerMonitor()
        for t in range(50):
            assert not mon.record(t, 1.0 + 0.01 * (t % 3))
