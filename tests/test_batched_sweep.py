"""Batched sweep engine (``sweep(mode="batched")``) and its device math.

Covers:

  * cell identity — batched cells equal measure-mode cells on every
    deterministic field, per (workload, strategy) pair, over no-crash,
    dense torn-survival (line AND word granularity), eviction-mode and
    multi-sample plans;
  * mode validation — batched requires the fork engine;
  * word-granularity refinement properties (the TornSpec
    ``granularity="word"`` axis): at fraction 1.0 the word survivor
    spans tile exactly the line survivor spans (same persisted bytes,
    same crash image); at fraction 0.0 word mode is bit-identical to
    the bare all-or-nothing crash — on the shared selection routines,
    on both emulator backends, and through the sweep;
  * device-math launches — ``cg_invariant_errors`` / ``mm_chunk_stats``
    / the KV and cache-op kernels vs numpy and host oracles, across
    chunked launches, with the per-kernel launch counter;
  * coverage — the figure matrices' device-math pairs take the analytic
    route with zero ``batched_fallback`` cells.
"""

import numpy as np
import pytest

from repro.core.backends import LineSurvival, select_survivors
from repro.core.backends.base import (
    entry_span,
    select_survivor_words,
    word_spans,
)
from repro.core.backends import batched
from repro.core.backends.batched import (
    LAUNCHES,
    cache_op_update,
    cg_invariant_errors,
    kv_row_checksums,
    kv_value_match,
    mm_chunk_stats,
    queue_validity,
)
from repro.core.nvm import CrashEmulator, NVMConfig
from repro.scenarios import (
    CrashPlan,
    TornSpec,
    deterministic_cell_dict,
    sweep,
)

SMALL = NVMConfig(cache_bytes=512 * 1024)

CG = ("cg", {"n": 512, "iters": 8, "seed": 5})
MM = ("mm", {"n": 32, "k": 8, "seed": 2})
XS = ("xsbench", {"lookups": 80, "grid_points": 600, "n_nuclides": 8,
                  "n_materials": 6, "max_nuclides_per_material": 4,
                  "flush_every_frac": 0.1, "seed": 7})


def _cell_key(c):
    return (c.workload, c.strategy, c.plan, c.crash_step, c.torn_survival)


# ---------------------------------------------------------------------------
# batched == measure cell identity
# ---------------------------------------------------------------------------

class TestBatchedEqualsMeasure:
    """The tentpole contract: every deterministic field of a batched
    cell equals the measure-mode cell, across every analytic evaluator
    (scratch, checkpoint, undo-log, per-workload adcc) and the
    measure-fallback pairs alike."""

    PLANS = (
        CrashPlan.no_crash(),
        CrashPlan.at_every_step(torn=TornSpec(0.5, seed=4, samples=2)),
        CrashPlan.at_every_step(torn=TornSpec(1.0, seed=2)),
        CrashPlan.at_fraction(0.6, torn=TornSpec(0.5, seed=3,
                                                 mode="eviction")),
        # sub-line torn images: the word-granularity axis
        CrashPlan.at_every_step(
            torn=TornSpec(0.5, seed=6, granularity="word")),
        CrashPlan.at_fraction(0.8, torn=TornSpec(0.25, seed=8,
                                                 granularity="word",
                                                 samples=2)),
    )
    STRATS = ("none", "adcc", "undo_log", "checkpoint_nvm@2")

    @pytest.mark.parametrize("wl", (CG, MM, XS), ids=lambda w: w[0])
    def test_batched_equals_measure_per_pair(self, wl):
        kw = dict(workloads=(wl,), strategies=self.STRATS,
                  plans=self.PLANS, cfg=SMALL)
        meas = sweep(engine="fork", mode="measure", **kw)
        batch = sweep(engine="fork", mode="batched", **kw)
        assert len(meas) == len(batch) > 0
        for m, b in zip(meas, batch):
            assert deterministic_cell_dict(b) == \
                deterministic_cell_dict(m), _cell_key(m)

    def test_batched_requires_fork_engine(self):
        with pytest.raises(ValueError):
            sweep(workloads=(CG,), strategies=("none",),
                  engine="rerun", mode="batched")

    def test_batched_workers_match_serial(self):
        kw = dict(workloads=(CG,), strategies=("adcc", "undo_log"),
                  plans=(CrashPlan.at_every_step(
                      torn=TornSpec(0.5, seed=4)),),
                  cfg=SMALL, mode="batched")
        serial = sweep(workers=1, **kw)
        sharded = sweep(workers=2, **kw)
        assert [deterministic_cell_dict(c) for c in sharded] == \
            [deterministic_cell_dict(c) for c in serial]

    def test_batched_cells_do_not_certify(self):
        # state_certified is a fork-measure extra; the analytic engine
        # never replays the golden tail, so it must stay None (and out
        # of the serialized dict), not False
        cells = sweep(workloads=(CG,), strategies=("checkpoint_nvm@2",),
                      plans=(CrashPlan.at_step(5,
                                               torn=TornSpec(0.5, seed=6)),),
                      cfg=SMALL, engine="fork", mode="batched")
        (c,) = cells
        assert c.state_certified is None
        assert "state_certified" not in c.to_json_dict()


class TestKVBatchedEqualsMeasure:
    """The KV family's analytic evaluators (PR 10): state-restoring
    strategies audited from the request oracle, adcc replayed from the
    crash image with stacked SplitMix64 checksum launches. Every cell
    must be byte-identical to measure mode AND actually take the
    analytic route (zero ``batched_fallback`` markers)."""

    PLANS = (
        CrashPlan.no_crash(),
        CrashPlan.at_every_step(torn=TornSpec(0.5, seed=4, samples=2)),
        CrashPlan.at_every_step(
            torn=TornSpec(0.5, seed=6, granularity="word")),
        CrashPlan.at_fraction(0.6, torn=TornSpec(0.25, seed=3,
                                                 mode="eviction")),
    )
    STRATS = ("none", "adcc", "shadow_snapshot", "undo_log",
              "checkpoint_nvm@2")

    @pytest.mark.parametrize("profile", ["etc", "udb"])
    def test_kv_batched_equals_measure(self, profile):
        wl = ("kv", {"profile": profile, "n_steps": 10, "seed": 11})
        kw = dict(workloads=(wl,), strategies=self.STRATS,
                  plans=self.PLANS, cfg=SMALL)
        meas = sweep(engine="fork", mode="measure", **kw)
        batch = sweep(engine="fork", mode="batched", **kw)
        assert len(meas) == len(batch) > 0
        for m, b in zip(meas, batch):
            assert deterministic_cell_dict(b) == \
                deterministic_cell_dict(m), _cell_key(m)
            assert "batched_fallback" not in b.info, _cell_key(b)

    def test_kv_blind_policy_batched_equals_measure(self):
        # blind adcc adopts the rawest root and serves torn state: the
        # image-side audit must reproduce the violation counts exactly
        wl = ("kv", {"profile": "udb", "n_steps": 10, "seed": 11,
                     "policy": "blind"})
        kw = dict(workloads=(wl,), strategies=("adcc",),
                  plans=(CrashPlan.at_every_step(
                      torn=TornSpec(0.5, seed=9, samples=2)),), cfg=SMALL)
        meas = sweep(engine="fork", mode="measure", **kw)
        batch = sweep(engine="fork", mode="batched", **kw)
        assert len(meas) == len(batch) > 0
        # the torn matrix must exercise real violations or the audit
        # replication is vacuous
        assert any(c.info.get("durability_violations", 0) > 0
                   or c.info.get("atomicity_violations", 0) > 0
                   for c in meas)
        for m, b in zip(meas, batch):
            assert deterministic_cell_dict(b) == \
                deterministic_cell_dict(m), _cell_key(m)
            assert "batched_fallback" not in b.info, _cell_key(b)

    def test_unsupported_strategy_cells_carry_fallback_reason(self):
        from repro.scenarios.strategies import (CheckpointStrategy,
                                                register_strategy)

        class _OddCheckpoint(CheckpointStrategy):
            pass

        register_strategy("odd_ckpt_pr10", _OddCheckpoint, override=True)
        cells = sweep(workloads=(CG,), strategies=("odd_ckpt_pr10",),
                      plans=(CrashPlan.at_step(3),), cfg=SMALL,
                      engine="fork", mode="batched")
        (c,) = cells
        assert c.info["batched_fallback"].startswith("unsupported")


# ---------------------------------------------------------------------------
# word-granularity refinement properties (satellite: TornSpec word axis)
# ---------------------------------------------------------------------------

class TestWordGranularityRefinement:
    """``granularity="word"`` refines the line model, it does not
    change its envelope: at fraction 1.0 the selected word spans tile
    exactly the full entry spans, and at fraction 0.0 nothing
    survives — so the two endpoints must reproduce the line-mode and
    bare-crash images bit for bit."""

    ORDER = [("b", 3), ("a", 0), ("a", 2), ("b", 1), ("a", 1)]
    GEOM = {"a": (8, 70, 8), "b": (8, 32, 8)}   # (epe, n_elems, itemsize)

    def _geometry(self, name):
        return self.GEOM[name]

    @pytest.mark.parametrize("mode", ["random", "eviction"])
    def test_full_fraction_word_spans_tile_line_spans(self, mode):
        words = select_survivor_words(
            self.ORDER, LineSurvival(1.0, seed=3, mode=mode,
                                     granularity="word"), self._geometry)
        lines = select_survivors(self.ORDER,
                                 LineSurvival(1.0, seed=3, mode=mode))
        assert sorted(lines) == sorted(self.ORDER)
        by_entry = {}
        for name, entry, lo, hi in words:
            assert hi > lo
            by_entry.setdefault((name, entry), []).append((lo, hi))
        assert set(by_entry) == set(self.ORDER)
        for name, entry in lines:
            epe, n_elems, _item = self.GEOM[name]
            spans = sorted(by_entry[(name, entry)])
            # contiguous, non-overlapping, covering the clipped span
            assert spans[0][0] == entry_span(entry, epe, n_elems)[0]
            assert spans[-1][1] == entry_span(entry, epe, n_elems)[1]
            for (_, h), (l2, _) in zip(spans, spans[1:]):
                assert h == l2
        # equal persisted element count -> equal persisted bytes
        n_word_elems = sum(hi - lo for _, _, lo, hi in words)
        n_line_elems = sum(
            entry_span(e, *self.GEOM[n][:2])[1]
            - entry_span(e, *self.GEOM[n][:2])[0] for n, e in lines)
        assert n_word_elems == n_line_elems

    def test_zero_fraction_selects_nothing(self):
        for mode in ("random", "eviction"):
            assert select_survivor_words(
                self.ORDER, LineSurvival(0.0, seed=1, mode=mode,
                                         granularity="word"),
                self._geometry) == []
        assert select_survivor_words(self.ORDER, None, self._geometry) == []

    def test_word_spans_respect_itemsize_and_clipping(self):
        # 8-byte words over f64 (itemsize 8): one element per word;
        # the last entry of a 70-element region clips at 70
        assert word_spans(8, 8, 70, 8) == [(i, i + 1) for i in range(64, 70)]
        # 4-byte items: two elements per word
        assert word_spans(0, 8, 32, 4) == [(0, 2), (2, 4), (4, 6), (6, 8)]
        # an element wider than a word can never split
        assert word_spans(0, 4, 16, 16) == [(0, 1), (1, 2), (2, 3), (3, 4)]

    def _traced_emu(self, backend, seed):
        emu = CrashEmulator(NVMConfig(backend=backend, cache_bytes=16 * 64,
                                      line_bytes=64))
        r = emu.alloc("x", (300,), np.float64)
        rng = np.random.default_rng(seed)
        for lo, w in zip(rng.integers(0, 250, 25), rng.integers(1, 40, 25)):
            r[int(lo):int(lo) + int(w)] = rng.uniform(size=int(w))
        return emu, r

    @pytest.mark.parametrize("backend", ["reference", "vectorized"])
    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_emulator_full_fraction_word_image_equals_line_image(
            self, backend, seed):
        a, ra = self._traced_emu(backend, seed)
        b, rb = self._traced_emu(backend, seed)
        a.crash(LineSurvival(1.0, seed=9, granularity="line"))
        b.crash(LineSurvival(1.0, seed=9, granularity="word"))
        assert np.array_equal(ra.nvm, rb.nvm)
        assert (a.stats.torn_bytes_persisted
                == b.stats.torn_bytes_persisted > 0)

    @pytest.mark.parametrize("backend", ["reference", "vectorized"])
    def test_emulator_zero_fraction_word_equals_bare_crash(self, backend):
        a, ra = self._traced_emu(backend, 7)
        b, rb = self._traced_emu(backend, 7)
        lost_a = a.crash()
        lost_b = b.crash(LineSurvival(0.0, seed=3, granularity="word"))
        assert lost_a == lost_b
        assert np.array_equal(ra.nvm, rb.nvm)
        assert b.stats.torn_bytes_persisted == 0

    @pytest.mark.parametrize("seed", range(6))
    def test_backends_agree_on_word_granularity_crashes(self, seed):
        rng = np.random.default_rng(400 + seed)
        frac = float(rng.choice([0.25, 0.5, 0.75]))
        mode = str(rng.choice(["random", "eviction"]))
        surv = LineSurvival(frac, seed=int(rng.integers(1 << 16)),
                            mode=mode, granularity="word")
        ref, r_ref = self._traced_emu("reference", seed)
        vec, r_vec = self._traced_emu("vectorized", seed)
        assert ref.crash(surv) == vec.crash(surv)
        assert np.array_equal(r_ref.nvm, r_vec.nvm)
        assert (ref.stats.torn_bytes_persisted
                == vec.stats.torn_bytes_persisted)

    def test_word_survival_describe_is_tagged(self):
        assert LineSurvival(0.5, 3).describe() == "random:f0.5:s3"
        assert LineSurvival(0.5, 3, granularity="word").describe() == \
            "random:f0.5:s3:word"
        with pytest.raises(ValueError):
            LineSurvival(0.5, granularity="byte")

    def test_sweep_word_fraction_endpoints_match_line_model(self):
        # through the full stack: fraction-1.0 word cells carry the
        # same recovery outcome as fraction-1.0 line cells; fraction
        # 0.0 matches the line 0.0 cells (both == bare torn crash)
        for frac in (0.0, 1.0):
            kw = dict(workloads=(CG,), strategies=("undo_log",),
                      cfg=SMALL, mode="measure")
            (line,) = sweep(plans=(CrashPlan.at_step(
                5, torn=TornSpec(frac, seed=2)),), **kw)
            (word,) = sweep(plans=(CrashPlan.at_step(
                5, torn=TornSpec(frac, seed=2, granularity="word")),), **kw)
            dl = deterministic_cell_dict(line)
            dw = deterministic_cell_dict(word)
            for d in (dl, dw):
                d.pop("plan")
                d.pop("torn_survival", None)
            assert dl == dw, frac


# ---------------------------------------------------------------------------
# device math vs oracles
# ---------------------------------------------------------------------------

class TestBatchedDeviceMath:
    def _cg_batch(self, seed, T=5, n=24):
        rng = np.random.default_rng(seed)
        P, Q, R, Z = (rng.normal(size=(T, n)) for _ in range(4))
        b = rng.normal(size=n)
        S = rng.normal(size=(n, n))
        S = 0.5 * (S + S.T)
        return P, Q, R, Z, b, S

    def _slabs_of(self, S):
        # dense matrix as full-width slabs: every column is a "nonzero"
        n = S.shape[0]
        cols = np.tile(np.arange(n, dtype=np.int32), (n, 1))
        return S.copy(), cols

    def _cg_oracle(self, P, Q, R, Z, b, S):
        orth = (np.abs(np.sum(P * Q, axis=1))
                / (np.linalg.norm(P, axis=1)
                   * np.linalg.norm(Q, axis=1) + 1e-300))
        rel = (np.linalg.norm(R - (b[None, :] - Z @ S), axis=1)
               / (np.linalg.norm(b) + 1e-300))
        return orth, rel

    def test_cg_errors_match_numpy_oracle(self):
        P, Q, R, Z, b, S = self._cg_batch(0)
        want_orth, want_rel = self._cg_oracle(P, Q, R, Z, b, S)
        orth, rel = cg_invariant_errors(P, Q, R, Z, b, *self._slabs_of(S))
        np.testing.assert_allclose(orth, want_orth, rtol=1e-12)
        np.testing.assert_allclose(rel, want_rel, rtol=1e-10)

    def test_cg_errors_across_chunked_launches(self):
        # more rows than one launch holds: every chunk is padded to the
        # fixed launch width and the results stitch back in order
        P, Q, R, Z, b, S = self._cg_batch(1, T=batched.SPARSE_BLOCK_ROWS + 7,
                                          n=16)
        want_orth, want_rel = self._cg_oracle(P, Q, R, Z, b, S)
        before = LAUNCHES[("cg_errors", "cpu")]
        orth, rel = cg_invariant_errors(P, Q, R, Z, b, *self._slabs_of(S))
        assert LAUNCHES[("cg_errors", "cpu")] - before == 2
        np.testing.assert_allclose(orth, want_orth, rtol=1e-12)
        np.testing.assert_allclose(rel, want_rel, rtol=1e-10)

    def test_cg_operator_slabs_sum_duplicate_entries(self):
        # CSR rows may repeat a column; the slab operator must sum them
        # exactly like the host's scatter-add _sym_matvec
        from repro.algorithms.cg import CsrMatrix, _sym_matvec
        from repro.scenarios.batched_engine import _CGAdccEvaluator

        indptr = np.array([0, 3, 4, 6, 7])
        indices = np.array([0, 2, 2, 1, 3, 3, 0], dtype=np.int32)
        data = np.array([4.0, 1.0, 0.5, 3.0, 2.0, -1.0, 0.25])
        A = CsrMatrix(n=4, indptr=indptr, indices=indices, data=data)
        ev = _CGAdccEvaluator.__new__(_CGAdccEvaluator)
        ev._A, ev._n, ev._op = A, 4, None
        vals, cols = ev._operator()
        x = np.array([1.0, -2.0, 0.5, 3.0])
        np.testing.assert_allclose(np.sum(vals * x[cols], axis=1),
                                   _sym_matvec(A, x), rtol=1e-15)

    def _mm_batch(self, seed, B=4, m=17):
        rng = np.random.default_rng(seed)
        V = np.zeros((B, m, m))
        V[:, :-1, :-1] = rng.normal(size=(B, m - 1, m - 1))
        V[:, :-1, -1] = V[:, :-1, :-1].sum(axis=2)
        V[:, -1, :-1] = V[:, :-1, :-1].sum(axis=1)
        return V

    @pytest.mark.parametrize("chunk_elems", [None, 17 * 17])
    def test_mm_stats_match_numpy_oracle(self, chunk_elems, monkeypatch):
        # chunk_elems=17*17 forces one slab per launch
        if chunk_elems is not None:
            monkeypatch.setattr(batched, "CHUNK_ELEMS", chunk_elems)
        V = self._mm_batch(3)
        V[1] = 0.0                    # an all-lost chunk image
        V[2, 4, 5] += 7.5             # one corrupted element
        before = LAUNCHES[("mm_stats", "cpu")]
        nonzero, absmax, rowmax, colmax = mm_chunk_stats(V)
        assert LAUNCHES[("mm_stats", "cpu")] - before == \
            (1 if chunk_elems is None else len(V))
        np.testing.assert_array_equal(nonzero, V.any(axis=(1, 2)))
        np.testing.assert_allclose(absmax, np.abs(V).max(axis=(1, 2)))
        want_row = np.abs(V[:, :-1, -1]
                          - V[:, :-1, :-1].sum(axis=2)).max(axis=1)
        want_col = np.abs(V[:, -1, :-1]
                          - V[:, :-1, :-1].sum(axis=1)).max(axis=1)
        np.testing.assert_allclose(rowmax, want_row, atol=1e-9)
        np.testing.assert_allclose(colmax, want_col, atol=1e-9)
        # intact slabs have ~0 residual; the corrupted one stands out
        assert rowmax[0] < 1e-9 and rowmax[2] > 1.0

    def test_kv_row_checksums_match_host_mixer(self):
        from repro.scenarios.kv import _mix_words

        rng = np.random.default_rng(6)
        rows = rng.integers(-(1 << 40), 1 << 40, size=(37, 7),
                            dtype=np.int64)
        got = kv_row_checksums(rows)
        want = np.array([_mix_words(r) for r in rows], dtype=np.int64)
        np.testing.assert_array_equal(got, want)
        assert kv_row_checksums(np.empty((0, 7), np.int64)).shape == (0,)

    def test_kv_value_match_matches_host_values(self):
        from repro.scenarios.kv import _value_words

        rng = np.random.default_rng(7)
        keys = rng.integers(0, 40, size=12).astype(np.int64)
        seqs = rng.integers(1, 99, size=12).astype(np.int64)
        nws = rng.integers(1, 9, size=12).astype(np.int64)
        got = np.zeros((12, 8), np.int64)
        for i in range(12):
            got[i, :nws[i]] = _value_words(int(keys[i]), int(seqs[i]),
                                           int(nws[i]))
        got[3, 0] ^= 1                     # one corrupted word
        ok = kv_value_match(keys, seqs, got, nws)
        want = np.ones(12, bool)
        want[3] = False
        np.testing.assert_array_equal(ok, want)

    @pytest.mark.parametrize("fifo", [False, True])
    @pytest.mark.parametrize("is_write", [False, True])
    def test_cache_op_update_matches_naive_transition(self, fifo, is_write):
        rng = np.random.default_rng(8)
        m = 23
        present = rng.random(m) < 0.6
        dirty = present & (rng.random(m) < 0.5)
        stamp = rng.integers(1, 50, size=m).astype(np.int64)
        t0 = 100
        new_p, new_d, new_s, miss, n_miss = cache_op_update(
            present.copy(), dirty.copy(), stamp.copy(), t0, is_write, fifo)
        assert new_p.all()
        np.testing.assert_array_equal(miss, ~present)
        assert n_miss == int((~present).sum())
        pos = np.arange(m, dtype=np.int64)
        if fifo:                           # hits keep their stamp
            np.testing.assert_array_equal(
                new_s, np.where(~present, t0 + pos, stamp))
        else:                              # LRU: every touch restamps
            np.testing.assert_array_equal(new_s, t0 + pos)
        want_d = np.ones(m, bool) if is_write else (dirty & present)
        np.testing.assert_array_equal(new_d, want_d)

    def test_queue_validity_matches_naive_scan(self):
        rng = np.random.default_rng(9)
        n = 40
        present = rng.random(n) < 0.7
        stamp = rng.integers(1, 30, size=n).astype(np.int64)
        ents = rng.integers(0, n, size=17).astype(np.int64)
        stamps = np.where(rng.random(17) < 0.5, stamp[ents],
                          stamp[ents] - 1).astype(np.int64)
        valid, wts = queue_validity(present, stamp, ents, stamps, 3)
        want_valid = present[ents] & (stamp[ents] == stamps)
        np.testing.assert_array_equal(valid, want_valid)
        np.testing.assert_array_equal(wts, np.where(want_valid, 3, 0))


class TestFigureMatricesTakeTheDevicePath:
    """The figure matrices whose evaluators launch device math — fig_torn
    (cg, adcc) / (mm, adcc) and fig_kv's adcc profiles — must evaluate
    every crashed cell analytically: zero ``batched_fallback`` cells,
    and a launch of each kernel they lean on."""

    @pytest.mark.parametrize("figure", ["fig_torn", "fig_kv"])
    def test_smoke_matrix_has_no_fallback_cells(self, figure):
        import importlib

        fig = importlib.import_module(f"benchmarks.{figure}")
        kw = fig._sweep_kw(smoke=True)
        wls = (kw["workloads"][:2] if figure == "fig_torn"
               else kw["workloads"])
        kernels = (("cg_errors", "mm_stats") if figure == "fig_torn"
                   else ("kv_row_checksums", "kv_value_match"))
        before = {k: LAUNCHES[(k, "cpu")] for k in kernels}
        cells = sweep(workloads=wls, strategies=("adcc",), plans=kw["plans"],
                      cfg=kw["cfg"], engine="fork", mode="batched")
        assert cells
        assert [c for c in cells if "batched_fallback" in c.info] == []
        for k in kernels:
            assert LAUNCHES[(k, "cpu")] > before[k], k
