"""Tests for the unified scenario layer (repro.scenarios).

Covers: CrashPlan resolution, the strategy registry, strategy
equivalence (every registered strategy on every workload recovers to a
correct final answer for a fixed seeded crash plan), byte-identity of
no-crash scenario runs against the pre-refactor direct-call paths
(driven through the same primitives the old ``run()`` loops used,
including TrafficStats), the batched sweep driver + its JSON artifact,
the central mechanism cost model, and the deprecation shims.
"""

import json

import numpy as np
import pytest

from repro.algorithms.cg import ADCC_CG, make_spd_system
from repro.algorithms.mm_abft import ABFTMatmul
from repro.algorithms.xsbench import ADCC_XSBench, XSBenchConfig
from repro.core import abft
from repro.core.nvm import NVMConfig
from repro.scenarios import (
    FORK_ONLY_FIELDS,
    FULL_RUN_FIELDS,
    STRATEGIES,
    WALL_CLOCK_FIELDS,
    CrashPlan,
    cg_step_profile,
    deterministic_cell_dict,
    make_strategy,
    make_workload,
    measure_divergence_fields,
    mechanism_cases,
    mechanism_step_seconds,
    run_scenario,
    sweep,
)

SMALL = NVMConfig(cache_bytes=512 * 1024)

CG = ("cg", {"n": 1024, "iters": 8, "seed": 3})
MM = ("mm", {"n": 64, "k": 16, "seed": 1})
XS = ("xsbench", {"lookups": 600, "grid_points": 800, "n_nuclides": 8,
                  "n_materials": 6, "max_nuclides_per_material": 4,
                  "flush_every_frac": 0.02, "seed": 7})
ALL_WORKLOADS = (CG, MM, XS)
ALL_STRATEGIES = ("none", "adcc", "undo_log", "checkpoint_hdd",
                  "checkpoint_nvm", "checkpoint_nvm_dram")


class TestCrashPlan:
    def _wl(self):
        wl = make_workload(CG)
        wl.setup(SMALL, "adcc")
        return wl

    def test_no_crash(self):
        (pt,) = CrashPlan.no_crash().resolve(self._wl())
        assert pt.step is None

    def test_at_step(self):
        (pt,) = CrashPlan.at_step(5).resolve(self._wl())
        assert pt.step == 5 and not pt.torn

    def test_at_step_out_of_range(self):
        with pytest.raises(ValueError):
            CrashPlan.at_step(99).resolve(self._wl())

    def test_at_fraction_endpoints(self):
        wl = self._wl()
        assert CrashPlan.at_fraction(0.0).resolve(wl)[0].step == 0
        assert CrashPlan.at_fraction(1.0).resolve(wl)[0].step == wl.n_steps - 1

    def test_at_phase_mm(self):
        wl = make_workload(MM)
        wl.setup(SMALL, "adcc")
        (pt,) = CrashPlan.at_phase("loop2", 1).resolve(wl)
        assert pt.step == wl._impl.nchunks + 1
        with pytest.raises(ValueError):
            CrashPlan.at_phase("loop3", 0).resolve(wl)

    def test_random_count_beyond_steps_raises(self):
        with pytest.raises(ValueError):
            CrashPlan.random(count=99, seed=0).resolve(self._wl())

    def test_random_is_seeded_and_batched(self):
        wl = self._wl()
        a = CrashPlan.random(count=3, seed=11).resolve(wl)
        b = CrashPlan.random(count=3, seed=11).resolve(wl)
        c = CrashPlan.random(count=3, seed=12).resolve(wl)
        assert [p.step for p in a] == [p.step for p in b]
        assert len(a) == 3 and len({p.step for p in a}) == 3
        assert [p.step for p in a] != [p.step for p in c]

    def test_describe(self):
        assert CrashPlan.no_crash().describe() == "no_crash"
        assert CrashPlan.at_step(4, torn=True).describe() == "step:4:torn"
        assert CrashPlan.at_phase("loop1", 2).describe() == "phase:loop1:2"


class TestRegistries:
    def test_strategy_registry_complete(self):
        assert set(ALL_STRATEGIES) <= set(STRATEGIES)

    def test_interval_variant_parsing(self):
        s = make_strategy("checkpoint_nvm@5")
        assert s.interval == 5 and s.name == "checkpoint_nvm@5"
        assert make_strategy("adcc").interval == 1

    def test_unknown_names_raise(self):
        with pytest.raises(ValueError, match=r"unknown strategy 'paxos'"):
            make_strategy("paxos")
        with pytest.raises(ValueError, match=r"unknown workload 'hpcg'"):
            make_workload("hpcg")

    def test_unknown_names_suggest_closest(self):
        with pytest.raises(ValueError, match=r"did you mean 'undo_log'"):
            make_strategy("undolog")
        with pytest.raises(ValueError, match=r"did you mean 'xsbench'"):
            make_workload("xsbnech")


class TestStrategyEquivalence:
    """For a fixed seeded CrashPlan, every registered strategy on every
    workload recovers to a correct final answer."""

    PLAN = CrashPlan.at_fraction(0.5)

    @pytest.mark.parametrize("strategy", ALL_STRATEGIES)
    @pytest.mark.parametrize("workload", ALL_WORKLOADS,
                             ids=[w[0] for w in ALL_WORKLOADS])
    def test_recovers_correct_answer(self, workload, strategy):
        res = run_scenario(workload, strategy, self.PLAN, cfg=SMALL)
        assert res.crash_step is not None
        assert res.correct, (workload[0], strategy, res.metrics)

    @pytest.mark.parametrize("strategy", ALL_STRATEGIES)
    def test_xsbench_counts_exactly_match_no_crash(self, strategy):
        ref = run_scenario(XS, "adcc", CrashPlan.no_crash(), cfg=SMALL)
        res = run_scenario(XS, strategy, self.PLAN, cfg=SMALL)
        assert np.array_equal(res.info["counts"], ref.info["counts"])

    def test_torn_crash_exercises_undo_log_rollback(self):
        res = run_scenario(CG, "undo_log", CrashPlan.at_step(5, torn=True),
                           cfg=SMALL)
        assert res.info["rolled_back"] is True
        assert res.steps_lost == 1 and res.restart_point == 4
        assert res.correct

    def test_checkpoint_interval_bounds_loss(self):
        res = run_scenario(CG, "checkpoint_nvm@3", CrashPlan.at_step(7),
                           cfg=SMALL)
        # checkpoints at steps 2 and 5; crash after step 7 loses 6..7
        assert res.restart_point == 5 and res.steps_lost == 2
        assert res.correct

    def test_undo_log_interval_commits_every_k_steps(self):
        # commits at steps 2 and 5; a crash at 7 leaves the 6..7 tx open
        # and rolls it back to the step-5 commit point
        res = run_scenario(CG, "undo_log@3", CrashPlan.at_step(7), cfg=SMALL)
        assert res.info["rolled_back"] is True
        assert res.restart_point == 5 and res.steps_lost == 2
        assert res.correct

    def test_adcc_interval_variant_is_rejected(self):
        with pytest.raises(ValueError):
            make_strategy("adcc@5")

    def test_strategy_instance_reuse_across_runs(self):
        # per-run state must reset on attach: the second run crashes
        # before its first checkpoint and must restart from scratch, not
        # resume from the first run's checkpoint step
        strat = make_strategy("checkpoint_nvm@4")
        first = run_scenario(CG, strat, CrashPlan.at_step(5), cfg=SMALL)
        assert first.restart_point == 3
        second = run_scenario(CG, strat, CrashPlan.at_step(2), cfg=SMALL)
        assert second.restart_point == -1 and second.resume_step == 0
        assert second.correct

    def test_mm_phase_crash_reports_loop(self):
        res = run_scenario(MM, "adcc", CrashPlan.at_phase("loop2", 1),
                           cfg=SMALL)
        assert res.info["crashed_in"] == "loop2"
        assert res.correct


class TestNoCrashByteIdentity:
    """no_crash scenario runs are byte-identical — results *and*
    emulator traffic — to the pre-refactor direct-call loops, driven
    here through the same primitives old ``run()`` used."""

    def _traffic(self, emu):
        s = emu.stats
        return {"nvm_bytes_written": s.nvm_bytes_written,
                "nvm_bytes_read": s.nvm_bytes_read,
                "lines_flushed": s.lines_flushed,
                "lines_evicted": s.lines_evicted,
                "torn_bytes_persisted": s.torn_bytes_persisted,
                "torn_entries_persisted": s.torn_entries_persisted}

    def test_cg(self):
        A, b = make_spd_system(1024, nnz_per_row=8, seed=3)
        cg = ADCC_CG(A, b, iters=8, cfg=SMALL)
        rho = cg._init_iterates()
        for i in range(8):
            rho = cg._iterate(i, rho)
        z_direct = cg.z.get(8)

        res = run_scenario(CG, "adcc", CrashPlan.no_crash(), cfg=SMALL)
        assert np.array_equal(res.info["z"], z_direct)
        assert res.traffic == self._traffic(cg.emu)
        assert res.modeled_total_seconds == cg.emu.modeled_seconds()

    def test_mm(self):
        rng = np.random.default_rng(1)
        A = rng.uniform(-1, 1, (64, 64))
        B = rng.uniform(-1, 1, (64, 64))
        mm = ABFTMatmul(A, B, 16, SMALL)
        for s in range(mm.nchunks):
            mm._loop1_chunk(s)
        for bi in range(len(mm.row_blocks)):
            mm._loop2_block(bi)
        C_direct = abft.strip(mm.C_temp.view.copy())

        res = run_scenario(MM, "adcc", CrashPlan.no_crash(), cfg=SMALL)
        assert np.array_equal(res.info["C"], C_direct)
        assert res.traffic == self._traffic(mm.emu)

    def test_xsbench(self):
        cfg = XSBenchConfig(lookups=600, grid_points=800, n_nuclides=8,
                            n_materials=6, max_nuclides_per_material=4,
                            flush_every_frac=0.02, seed=7)
        xs = ADCC_XSBench(cfg, SMALL, policy="selective")
        for i in range(cfg.lookups):
            xs._lookup(i)
            if (i + 1) % xs.flush_every == 0:
                xs._flush_critical(i + 1)
        counts_direct = np.array([int(c.view[0]) for c in xs._counters])

        res = run_scenario(XS, "adcc", CrashPlan.no_crash(), cfg=SMALL)
        assert np.array_equal(res.info["counts"], counts_direct)
        assert np.array_equal(res.info["macro_xs"], xs._macro.view)
        assert res.traffic == self._traffic(xs.emu)


class TestSweep:
    def test_matrix_expansion_and_artifact(self, tmp_path):
        out = tmp_path / "BENCH_scenarios.json"
        cells = sweep(
            workloads=(CG, MM),
            strategies=("none", "adcc", "checkpoint_nvm@2"),
            plans=(CrashPlan.no_crash(), CrashPlan.at_fraction(0.5),
                   CrashPlan.random(count=2, seed=1)),
            cfg=SMALL, out_json=str(out))
        # 2 workloads x 3 strategies x (1 + 1 + 2) crash points
        assert len(cells) == 2 * 3 * 4
        assert all(c.correct for c in cells)

        payload = json.loads(out.read_text())
        assert payload["schema"] == "repro.scenarios.sweep/v1"
        assert len(payload["cells"]) == len(cells)
        cell = payload["cells"][0]
        for key in ("workload", "strategy", "plan", "crash_step",
                    "overhead_seconds", "steps_lost", "steps_recomputed",
                    "correct", "metrics", "traffic"):
            assert key in cell

    def test_random_plan_yields_distinct_cells(self):
        cells = sweep(workloads=(CG,), strategies=("adcc",),
                      plans=(CrashPlan.random(count=3, seed=5),), cfg=SMALL)
        steps = [c.crash_step for c in cells]
        assert len(steps) == 3 and len(set(steps)) == 3

    def test_unresolvable_cells_are_skipped_not_fatal(self, tmp_path):
        # "loop2" exists only for adcc-mode MM: the cg cells and the
        # plain-mode mm cell must be skipped while the matrix completes
        out = tmp_path / "s.json"
        cells = sweep(workloads=(CG, MM), strategies=("none", "adcc"),
                      plans=(CrashPlan.at_phase("loop2", 0),),
                      cfg=SMALL, out_json=str(out))
        assert len(cells) == 1
        assert cells[0].workload == "mm" and cells[0].strategy == "adcc"
        payload = json.loads(out.read_text())
        assert len(payload["skipped"]) == 3
        assert all(s["plan"] == "phase:loop2:0" for s in payload["skipped"])


class TestForkEngine:
    """The prefix-sharing fork engine must be observationally identical
    to from-scratch reruns: cell-for-cell equal deterministic payloads
    on matrices covering every strategy, torn crashes, batch plans, and
    phase-grounded plans."""

    WLS = (("cg", {"n": 512, "iters": 8, "seed": 3}),
           ("mm", {"n": 32, "k": 8, "seed": 1}),
           ("xsbench", {"lookups": 200, "grid_points": 400, "n_nuclides": 8,
                        "n_materials": 6, "max_nuclides_per_material": 4,
                        "flush_every_frac": 0.05, "seed": 7}))
    PLANS = (CrashPlan.no_crash(), CrashPlan.at_fraction(0.5),
             CrashPlan.at_fraction(0.8, torn=True),
             CrashPlan.random(count=2, seed=1),
             CrashPlan.at_phase("loop2", 1))

    def test_fork_equals_rerun_cell_for_cell(self):
        kw = dict(workloads=self.WLS, strategies=ALL_STRATEGIES,
                  plans=self.PLANS, cfg=SMALL)
        rerun = sweep(engine="rerun", **kw)
        fork = sweep(engine="fork", **kw)
        assert len(rerun) == len(fork) > 0
        for a, b in zip(rerun, fork):
            da, db = deterministic_cell_dict(a), deterministic_cell_dict(b)
            assert da == db, (a.workload, a.strategy, a.plan, a.crash_step)
        # wall-derived fields exist but are excluded from the contract
        assert set(WALL_CLOCK_FIELDS) <= set(rerun[0].to_json_dict())

    def test_fork_skips_same_ungroundable_cells(self, tmp_path):
        out_fork = tmp_path / "fork.json"
        out_rerun = tmp_path / "rerun.json"
        kw = dict(workloads=(CG, MM), strategies=("none", "adcc"),
                  plans=(CrashPlan.at_phase("loop2", 0),), cfg=SMALL)
        fork = sweep(engine="fork", out_json=str(out_fork), **kw)
        rerun = sweep(engine="rerun", out_json=str(out_rerun), **kw)
        assert [deterministic_cell_dict(c) for c in fork] == \
            [deterministic_cell_dict(c) for c in rerun]
        skipped_fork = json.loads(out_fork.read_text())["skipped"]
        skipped_rerun = json.loads(out_rerun.read_text())["skipped"]
        assert skipped_fork == skipped_rerun and len(skipped_fork) == 3

    def test_at_every_step_is_exhaustive(self):
        wl = make_workload(CG)
        wl.setup(SMALL, "adcc")
        points = CrashPlan.at_every_step().resolve(wl)
        assert [p.step for p in points] == list(range(wl.n_steps))
        assert CrashPlan.at_every_step(torn=True).describe() == "every:torn"

    def test_dense_every_step_sweep_forked(self):
        cells = sweep(workloads=(CG,), strategies=("adcc",),
                      plans=(CrashPlan.at_every_step(),), cfg=SMALL,
                      engine="fork")
        assert [c.crash_step for c in cells] == list(range(8))
        assert all(c.correct for c in cells)

    def test_unknown_engine_raises(self):
        with pytest.raises(ValueError):
            sweep(workloads=(CG,), strategies=("none",), engine="exec")

    def test_snapshot_restore_roundtrip_mid_run(self):
        """Workload+strategy snapshot at step k resumes to the same
        final answer and traffic as an uninterrupted run."""
        wl = make_workload(CG)
        wl.setup(SMALL, "adcc")
        strat = make_strategy("adcc")
        strat.attach(wl)
        for i in range(4):
            strat.before_step(i)
            wl.step(i)
            strat.after_step(i)
        snap, ssnap = wl.snapshot(), strat.snapshot()
        for i in range(4, wl.n_steps):
            strat.before_step(i)
            wl.step(i)
            strat.after_step(i)
        direct = wl.finalize()
        traffic = wl.emu.stats.nvm_bytes_written

        wl.restore_snapshot(snap)
        strat.restore_snapshot(ssnap)
        for i in range(4, wl.n_steps):
            strat.before_step(i)
            wl.step(i)
            strat.after_step(i)
        replay = wl.finalize()
        assert np.array_equal(replay.info["z"], direct.info["z"])
        assert wl.emu.stats.nvm_bytes_written == traffic


class TestMeasureMode:
    """mode="measure" stops each crashed cell after strategy recovery
    and computes its fields from the recovered state. Contract: the
    measured cell dict is a STRICT field-subset of the full-execution
    fork cell dict, equal on every shared deterministic field, and the
    omitted fields are exactly FULL_RUN_FIELDS."""

    WLS = TestForkEngine.WLS
    PLANS = (CrashPlan.no_crash(), CrashPlan.at_fraction(0.4),
             CrashPlan.at_fraction(0.8, torn=True))

    def test_measure_is_field_subset_of_fork_on_every_pair(self):
        # every strategy x workload smoke cell
        kw = dict(workloads=self.WLS, strategies=ALL_STRATEGIES,
                  plans=self.PLANS, cfg=SMALL)
        full = sweep(engine="fork", mode="full", **kw)
        meas = sweep(engine="fork", mode="measure", **kw)
        assert len(full) == len(meas) > 0
        for f, m in zip(full, meas):
            cell = (m.workload, m.strategy, m.plan, m.crash_step)
            assert measure_divergence_fields(m, f) == [], cell
            if m.crash_step is None:
                # no_crash cells always execute fully (tail-free anyway)
                assert deterministic_cell_dict(m) == \
                    deterministic_cell_dict(f), cell
            else:
                dm, df = m.to_json_dict(), f.to_json_dict()
                # the only fields a measured cell may ADD are the
                # fork-engine-local certification fields (full cells
                # check correctness by running the tail instead)
                assert set(dm) - set(df) <= set(FORK_ONLY_FIELDS), cell
                assert set(df) - set(dm) == set(FULL_RUN_FIELDS), cell

    def test_measure_is_engine_invariant(self):
        kw = dict(workloads=(CG,), strategies=("adcc", "undo_log@2"),
                  plans=(CrashPlan.at_every_step(),), cfg=SMALL,
                  mode="measure")
        fork = sweep(engine="fork", **kw)
        rerun = sweep(engine="rerun", **kw)
        assert [deterministic_cell_dict(c) for c in fork] == \
            [deterministic_cell_dict(c) for c in rerun]

    def test_measure_cells_skip_finalize_fields(self):
        (cell,) = sweep(workloads=(CG,), strategies=("checkpoint_nvm",),
                        plans=(CrashPlan.at_step(5),), cfg=SMALL,
                        mode="measure")
        assert cell.correct is None and cell.metrics is None
        assert cell.traffic is None and cell.modeled_total_seconds is None
        assert cell.steps_lost == 0 and cell.restart_point == 5
        assert cell.resume_seconds == 0.0

    def test_unknown_mode_raises(self):
        with pytest.raises(ValueError):
            sweep(workloads=(CG,), strategies=("none",), mode="partial")


class TestCorrectnessClass:
    """correctness_class is computed from the recovered state's
    bookkeeping — identical across engines and modes, and meaningful
    without finalize()."""

    def test_no_crash_is_complete(self):
        res = run_scenario(CG, "adcc", CrashPlan.no_crash(), cfg=SMALL)
        assert res.correctness_class == "complete"

    def test_checkpoint_rolls_back_consistently(self):
        res = run_scenario(CG, "checkpoint_nvm@3", CrashPlan.at_step(7),
                           cfg=SMALL)
        assert res.correctness_class == "consistent_rollback"

    def test_native_restarts_from_scratch(self):
        res = run_scenario(CG, "none", CrashPlan.at_step(5), cfg=SMALL)
        assert res.correctness_class == "scratch_restart"

    def test_unrecovered_crash(self):
        res = run_scenario(CG, "none", CrashPlan.at_step(5), cfg=SMALL,
                           recover=False)
        assert res.correctness_class == "unrecovered"

    def test_xsbench_basic_policy_loses_updates(self):
        # the paper's Fig.-10 failing scheme: the loop index is flushed
        # every lookup but the counters go stale in cache — recovery
        # resumes past updates that never persisted, and the class
        # (computed WITHOUT running the tail) flags exactly the cells
        # whose end-of-run counts come out wrong
        cfg = NVMConfig(cache_bytes=4096)
        res = run_scenario(
            ("xsbench", {"lookups": 400, "grid_points": 400,
                         "n_nuclides": 8, "n_materials": 6,
                         "max_nuclides_per_material": 4,
                         "flush_every_frac": 0.02, "seed": 7,
                         "policy": "basic"}),
            "adcc", CrashPlan.at_fraction(0.6), cfg=cfg)
        assert res.correctness_class == "lost_updates"
        assert res.correct is False


class TestSweepInvariance:
    """sweep() results depend only on the cell coordinates, not on
    listing order or execution sharding (the workers>1 CI gate)."""

    WLS = (("cg", {"n": 256, "iters": 6, "seed": 3}),
           ("mm", {"n": 32, "k": 8, "seed": 1}))
    STRATS = ("adcc", "checkpoint_nvm@2")
    PLANS = (CrashPlan.no_crash(), CrashPlan.at_fraction(0.5),
             CrashPlan.random(count=2, seed=1))

    @staticmethod
    def _keyed(cells):
        keyed = {(c.workload, c.strategy, c.plan, c.crash_step, c.torn):
                 deterministic_cell_dict(c) for c in cells}
        assert len(keyed) == len(cells)
        return keyed

    def test_results_invariant_to_listing_order(self):
        fwd = sweep(workloads=self.WLS, strategies=self.STRATS,
                    plans=self.PLANS, cfg=SMALL)
        rev = sweep(workloads=tuple(reversed(self.WLS)),
                    strategies=tuple(reversed(self.STRATS)),
                    plans=tuple(reversed(self.PLANS)), cfg=SMALL)
        assert self._keyed(fwd) == self._keyed(rev)

    @pytest.mark.parametrize("mode", ["full", "measure"])
    def test_workers_match_serial_cell_for_cell(self, mode):
        kw = dict(workloads=self.WLS, strategies=self.STRATS,
                  plans=self.PLANS, cfg=SMALL, mode=mode)
        serial = sweep(workers=1, **kw)
        sharded = sweep(workers=2, **kw)
        assert [deterministic_cell_dict(c) for c in sharded] == \
            [deterministic_cell_dict(c) for c in serial]

    def test_workers_skip_same_cells_deterministically(self, tmp_path):
        out1, out2 = tmp_path / "w1.json", tmp_path / "w2.json"
        kw = dict(workloads=(CG, MM), strategies=("none", "adcc"),
                  plans=(CrashPlan.at_phase("loop2", 0),), cfg=SMALL)
        sweep(workers=1, out_json=str(out1), **kw)
        sweep(workers=2, out_json=str(out2), **kw)
        p1, p2 = json.loads(out1.read_text()), json.loads(out2.read_text())
        assert p1["skipped"] == p2["skipped"] and len(p1["skipped"]) == 3

    def test_workers_require_picklable_specs(self):
        wl = make_workload(CG)
        with pytest.raises(ValueError):
            sweep(workloads=(wl,), strategies=("none", "adcc"),
                  plans=(CrashPlan.no_crash(),), cfg=SMALL, workers=2)
        with pytest.raises(ValueError):
            sweep(workloads=(CG, MM), strategies=(make_strategy("none"),),
                  plans=(CrashPlan.no_crash(),), cfg=SMALL, workers=2)

    def test_bad_workers_raises(self):
        with pytest.raises(ValueError):
            sweep(workloads=(CG,), strategies=("none",), workers=0)

    @pytest.mark.parametrize("mode,backend", [("batched", "vectorized"),
                                              ("measure", "device")])
    def test_device_math_shards_refused_on_an_accelerator(
            self, monkeypatch, mode, backend):
        # one process per chip: shards that would run device math may
        # neither share the parent's chip nor drop to the CPU unsaid
        import jax
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(ValueError, match="one process"):
            sweep(workloads=(CG, MM), strategies=("none", "adcc"),
                  plans=(CrashPlan.no_crash(),), mode=mode, workers=2,
                  cfg=NVMConfig(cache_bytes=512 * 1024, backend=backend))

    def test_host_only_shards_allowed_on_an_accelerator(self, monkeypatch):
        # measure cells on the vectorized backend run no device math:
        # their shards never ask which platform the parent defaults to
        import jax

        def no_backend_query():
            raise AssertionError("host-only sweep queried the JAX backend")

        monkeypatch.setattr(jax, "default_backend", no_backend_query)
        cells = sweep(workloads=(CG, MM), strategies=("none",),
                      plans=(CrashPlan.no_crash(),), cfg=SMALL,
                      mode="measure", workers=2)
        assert len(cells) == 2


class TestCostModel:
    def test_seven_mechanism_axis(self):
        names = [c.name for c in mechanism_cases()]
        assert names == ["native", "ckpt_hdd", "ckpt_nvm_only",
                         "ckpt_nvm_dram", "pmem_undo", "adcc_nvm_only",
                         "adcc_nvm_dram"]

    def test_cg_formulas_match_paper_model(self):
        cfg = NVMConfig(nvm_same_as_dram=True)
        n = 1024
        p = cg_step_profile(n, cfg.line_bytes)
        vec = n * 8
        line = cfg.line_bytes
        assert mechanism_step_seconds("none", p, cfg) == 0.0
        assert mechanism_step_seconds("checkpoint_hdd", p, cfg) == \
            pytest.approx(4 * vec / cfg.hdd_bw)
        assert mechanism_step_seconds("checkpoint_nvm", p, cfg) == \
            pytest.approx(4 * vec / cfg.write_bw
                          + (4 * vec // line) * cfg.flush_latency)
        assert mechanism_step_seconds("undo_log", p, cfg) == \
            pytest.approx(2 * (3 * vec / cfg.write_bw
                               + (3 * vec // line) * cfg.flush_latency))
        assert mechanism_step_seconds("adcc", p, cfg) == \
            pytest.approx(line / cfg.write_bw + cfg.flush_latency)

    def test_nvm_dram_checkpoint_pays_dram_cache_flush(self):
        p = cg_step_profile(1024, 64)
        nvm_only = NVMConfig(nvm_same_as_dram=True)
        nvm_dram = NVMConfig()
        extra = (mechanism_step_seconds("checkpoint_nvm_dram", p, nvm_dram)
                 - mechanism_step_seconds("checkpoint_nvm", p, nvm_dram))
        assert extra == pytest.approx(
            nvm_dram.dram_cache_bytes / nvm_dram.dram_bw
            + nvm_dram.dram_cache_bytes / nvm_dram.write_bw)
        assert mechanism_step_seconds("checkpoint_nvm", p, nvm_only) < \
            mechanism_step_seconds("checkpoint_nvm", p, nvm_dram)


class TestPolicyAndImplProfiles:
    def test_xsbench_every_policy_models_per_step_overhead(self):
        every = run_scenario(("xsbench", {**XS[1], "policy": "every"}),
                             "adcc", CrashPlan.no_crash(), cfg=SMALL)
        sel = run_scenario(XS, "adcc", CrashPlan.no_crash(), cfg=SMALL)
        # "every" flushes the full critical state each lookup; "selective"
        # every flush_every lookups — modeled overhead must reflect that
        flush_every = max(1, int(XS[1]["lookups"]
                                 * XS[1]["flush_every_frac"]))
        assert every.overhead_seconds == pytest.approx(
            sel.overhead_seconds * flush_every, rel=0.2)

    def test_xsbench_basic_policy_models_index_only_flush(self):
        basic = run_scenario(("xsbench", {**XS[1], "policy": "basic"}),
                             "adcc", CrashPlan.no_crash(), cfg=SMALL)
        every = run_scenario(("xsbench", {**XS[1], "policy": "every"}),
                             "adcc", CrashPlan.no_crash(), cfg=SMALL)
        # both flush per lookup, but basic persists one line, not ~11
        assert 0 < basic.overhead_seconds < every.overhead_seconds

    def test_prebuilt_impl_uses_its_own_oracle(self):
        from repro.scenarios import CGWorkload
        # non-default nnz/seed: the (n, nnz, seed) cache would build a
        # different system — correctness must be judged on the real one
        A, b = make_spd_system(512, nnz_per_row=4, seed=42)
        wl = CGWorkload(impl=ADCC_CG(A, b, iters=6, cfg=SMALL))
        res = run_scenario(wl, "adcc", CrashPlan.no_crash())
        assert res.correct and res.metrics["max_abs_err"] == 0.0


class TestDeprecationShims:
    def test_cg_run_warns_and_works(self):
        A, b = make_spd_system(512, seed=6)
        with pytest.warns(DeprecationWarning):
            res = ADCC_CG(A, b, iters=4, cfg=SMALL).run()
        assert res.iters_done == 4 and res.crashed_at is None

    def test_mm_run_warns_and_works(self):
        rng = np.random.default_rng(0)
        A, B = rng.uniform(-1, 1, (32, 32)), rng.uniform(-1, 1, (32, 32))
        with pytest.warns(DeprecationWarning):
            res = ABFTMatmul(A, B, 8, SMALL).run(crash_after=("loop1", 1))
        assert res.crashed_in == "loop1" and res.max_error < 1e-9

    def test_xsbench_run_warns_and_works(self):
        cfg = XSBenchConfig(lookups=200, grid_points=400, n_nuclides=8)
        with pytest.warns(DeprecationWarning):
            res = ADCC_XSBench(cfg, SMALL).run(crash_at=100)
        assert res.crashed_at == 100
        assert int(res.counts.sum()) == cfg.lookups
