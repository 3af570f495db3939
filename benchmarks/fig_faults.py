"""Beyond-paper figure: recovery under fire — nested-crash and
media-fault campaigns over every (workload, strategy) pair.

The torn-write figure (fig_torn) asks what a mechanism does with an
inconsistent *crash image*. This figure asks the two harder questions a
real NVM deployment adds on top:

* **nested crashes** — the machine crashes again *while recovery is
  running* (``FaultSpec(nested_after=k)`` re-crashes after k recovery
  actions, optionally with its own torn line survival). Each cell is
  certified against the *golden* single-crash cell — same crash, no
  fault — by restart point and state digest: ``recovery_idempotent``
  means the retried recovery provably landed on the same state
  (re-entrancy, proven not assumed); ``recovery_diverged`` means the
  mid-recovery crash changed the outcome — the crash-unsafe-recovery
  class WITCHER hunts. ABFT-MM's ADCC recovery used to *diverge* here
  (it re-executed compute chunks while advancing its progress counter
  mid-recovery, so a second crash stranded progress the data didn't
  back); recovery now replays chunks with the counter pinned at its
  crash-time value, so MM-adcc joins the wholesale mechanisms'
  rollback / restore paths under the zero-``recovery_diverged``
  coverage-floor gate (the old pinned-diverged finding, flipped —
  not deleted).

* **silent media faults** — a seeded poisoned-line/bit-flip injector
  (``FaultSpec(poison_words=w)``) corrupts the post-crash image with
  no torn-ness to flag it. Recovery must *detect* this through the
  integrity machinery it already has (CG's invariant scan, ABFT's
  checksums, the undo log's entry CRCs, KV's row checksums):
  ``fault_detected`` vs ``fault_silent`` (corruption reached the
  resumed run with no signal). Gate: the ADCC strategies produce zero
  ``fault_silent`` cells on the covered regions — the paper's claim
  that algorithm knowledge doubles as an integrity check, made
  falsifiable. The wholesale mechanisms split as the taxonomy
  predicts: checkpoint/shadow restore *heals* poison wholesale
  (harmless classes), and the undo log — whose commit-boundary cells
  used to let poison on committed spans through silently (the old
  pinned coverage hole) — now stamps a crc32 per committed span and
  validates the post-crash image against them, so it rides the same
  zero-``fault_silent`` floor on its covered spans.

Campaign sweeps run ``mode="measure"`` under the full dense-gate stack
(``run_dense_cross_checks``: sharded == serial cell-for-cell, every
measure field == full execution) at every size, plus the
campaign-specific gates above. ``--chaos`` additionally runs the
self-healing harness gate: a sharded sweep with one injected worker
kill and one injected hang must complete — via retry and re-dispatch —
cell-for-cell identical to the serial sweep. Sharded campaign sweeps
journal completed shards to ``BENCH_faults.partial.jsonl`` so an
interrupted run resumes instead of restarting.
"""

from __future__ import annotations

import os
from collections import Counter
from typing import Dict, Iterator, List, Tuple

from repro.core.nvm import NVMConfig
from repro.scenarios import CrashPlan, FaultSpec, sweep

from .common import ART, Row, write_json

ARTIFACT = "fig_faults.json"
BENCH_JSON = os.path.join(ART, "BENCH_faults.json")
JOURNAL = os.path.join(ART, "BENCH_faults.partial.jsonl")

SEED = 47

HPC_WORKLOADS = (
    ("cg", {"n": 2048, "iters": 12, "seed": 5}),
    ("mm", {"n": 64, "k": 16, "seed": 2}),
    ("xsbench", {"lookups": 160, "grid_points": 1200, "n_nuclides": 8,
                 "n_materials": 6, "max_nuclides_per_material": 4,
                 "flush_every_frac": 0.05, "seed": 7}),
)
SMOKE_HPC_WORKLOADS = (
    ("cg", {"n": 512, "iters": 8, "seed": 5}),
    ("mm", {"n": 32, "k": 8, "seed": 2}),
    ("xsbench", {"lookups": 80, "grid_points": 600, "n_nuclides": 8,
                 "n_materials": 6, "max_nuclides_per_material": 4,
                 "flush_every_frac": 0.1, "seed": 7}),
)
KV_WORKLOAD = ("kv", {"profile": "etc", "n_steps": 36, "seed": 11})
SMOKE_KV_WORKLOAD = ("kv", {"profile": "etc", "n_steps": 16, "seed": 11})

HPC_STRATEGIES = ("adcc", "undo_log", "checkpoint_nvm@2",
                  "shadow_snapshot@2")
KV_STRATEGIES = ("adcc", "shadow_snapshot@2")

# mechanisms whose recovery is rollback/restore over state they own —
# re-running it after a mid-recovery crash must land on the identical
# outcome, at every crash point (the nested-campaign coverage floor)
WHOLESALE_BASES = ("undo_log", "checkpoint_hdd", "checkpoint_nvm",
                   "checkpoint_nvm_dram", "shadow_snapshot")

# poison scope per workload: the regions the pair's integrity machinery
# actually covers, valid in BOTH plain and adcc modes (FaultSpec globs
# resolve against live-region names at recovery time). cg: all live
# iterate vectors (the invariant scan's domain; the "iter" counter is
# not live and stays clean — a garbage counter would send the backward
# scan out of bounds, a different failure than silent data corruption).
# mm: "C" in plain mode, the checksummed C_s chunks in adcc mode
# (C_temp's loop-1 rows carry no checksum yet — poison there is
# genuinely undetectable and would gate-fail by design, see the
# uncovered-region test). xsbench: the typed tally counters the
# counter/index cross-check covers. kv: the A/B-versioned hash index
# (row checksums); 8 words so the seeded sampler reliably hits
# committed-live slots, not just the inactive A/B halves.
POISON_REGIONS = {"cg": None, "mm": ("C", "C_s*"),
                  "xsbench": ("type_counter_*",), "kv": ("kv.index",)}
POISON_WORDS = {"cg": 2, "mm": 2, "xsbench": 2, "kv": 8}

NESTED_FAULTS = (
    # re-crash after the FIRST recovery action: the hardest re-entrancy
    # point (nothing of attempt 1 is guaranteed complete)
    FaultSpec(nested_after=1, seed=SEED),
    # deeper re-crash, and the second crash is itself torn: half the
    # dirty lines of the interrupted recovery survive
    FaultSpec(nested_after=3, nested_fraction=0.5, seed=SEED + 1),
)


def _fractions(smoke: bool) -> Tuple[float, ...]:
    return (0.35, 0.7) if smoke else (0.2, 0.5, 0.8)


def _nested_plans(smoke: bool) -> Tuple[CrashPlan, ...]:
    plans = [CrashPlan.at_fraction(f, fault=fs)
             for fs in NESTED_FAULTS for f in _fractions(smoke)]
    # a torn first crash + a nested re-crash during its recovery: the
    # compounded case (rollback of a torn image, interrupted)
    plans.append(CrashPlan.at_fraction(0.6, torn=True,
                                       fault=NESTED_FAULTS[0]))
    return tuple(plans)


def _poison_plans(wl_name: str, smoke: bool) -> Tuple[CrashPlan, ...]:
    words = POISON_WORDS[wl_name]
    regions = POISON_REGIONS[wl_name]
    plans = [CrashPlan.at_fraction(f, fault=FaultSpec(
        poison_words=words, seed=SEED + 10 + i, poison_regions=regions))
        for i, f in enumerate(_fractions(smoke))]
    # poison layered on a torn crash image: the detector must separate
    # the two corruption sources (never nested+poison combined — each
    # campaign isolates one fault axis)
    plans.append(CrashPlan.at_fraction(0.6, torn=True, fault=FaultSpec(
        poison_words=words, seed=SEED + 20, poison_regions=regions)))
    return tuple(plans)


def _campaign_sweeps(smoke: bool) -> Iterator[Tuple[str, Dict]]:
    """Every (campaign, sweep-kwargs) this figure runs: both campaigns
    over the HPC matrix and over the KV serving pair. Poison scopes are
    per-workload, so the poison campaign is one sweep per workload."""
    cfg = NVMConfig(cache_bytes=1024 * 1024)
    hpc = SMOKE_HPC_WORKLOADS if smoke else HPC_WORKLOADS
    kv = SMOKE_KV_WORKLOAD if smoke else KV_WORKLOAD
    for wls, strats in ((hpc, HPC_STRATEGIES), ((kv,), KV_STRATEGIES)):
        yield "nested", dict(workloads=wls, strategies=strats,
                             plans=_nested_plans(smoke), cfg=cfg)
        for wl in wls:
            yield "poison", dict(workloads=(wl,), strategies=strats,
                                 plans=_poison_plans(wl[0], smoke), cfg=cfg)


def _base(strategy: str) -> str:
    return strategy.partition("@")[0]


def check_fault_gates(campaign: str, kw: Dict, cells, workers: int) -> None:
    """Campaign gates on top of the shared dense-gate core. Explicit
    raises (not asserts): these are CI gates and must survive
    ``python -O``."""
    from .scenarios_sweep import run_dense_cross_checks

    run_dense_cross_checks(kw, cells, workers)

    crashed = [c for c in cells if c.crash_step is not None]
    for c in crashed:
        key = (c.workload, c.strategy, c.plan, c.crash_step)
        if int(c.info.get("fault_words_injected") or 0) == 0 \
                and int(c.info.get("nested_crashes") or 0) == 0 \
                and "recovery_attempts" not in c.info:
            raise AssertionError(
                f"fault-campaign cell ran without the fault harness: {key}")
        if campaign == "nested":
            # MM-adcc rides the same floor since its replay-pinned
            # counter fix: the old pinned-diverged finding, flipped
            if ((_base(c.strategy) in WHOLESALE_BASES
                    or (_base(c.strategy) == "adcc" and c.workload == "mm"))
                    and c.correctness_class == "recovery_diverged"):
                raise AssertionError(
                    f"recovery diverged under a nested crash (idempotence "
                    f"floor): {key}")
        else:
            if int(c.info.get("fault_words_injected") or 0) == 0:
                raise AssertionError(
                    f"poison cell injected zero words (mis-scoped "
                    f"poison_regions?): {key}")
            # undo_log joined the zero-silent floor when commits began
            # stamping per-span payload crc32s (the old coverage-hole
            # pin, flipped): every campaign poison scope is tx-covered
            if (_base(c.strategy) in ("adcc", "undo_log")
                    and c.correctness_class == "fault_silent"):
                raise AssertionError(
                    f"integrity machinery missed a poisoned-line "
                    f"fault on a covered region: {key}")
    if campaign == "nested":
        # the trap must actually fire somewhere for every strategy whose
        # recovery performs persistent actions — a campaign whose nested
        # crashes never trigger certifies nothing. (KV ADCC recovery is
        # read-mostly: its blind/validate scan only writes when torn
        # rows must be dropped, so it is exempt from the floor.)
        fired = Counter()
        for c in crashed:
            fired[c.strategy] += int(c.info.get("nested_crashes") or 0)
        exempt = {"adcc"} if kw["workloads"][0][0] == "kv" else set()
        for strategy in kw["strategies"]:
            if strategy in exempt:
                continue
            if fired[strategy] == 0:
                raise AssertionError(
                    f"nested campaign never interrupted {strategy!r} "
                    f"recovery (trap count 0 across all cells)")


def check_chaos_gate(smoke: bool) -> int:
    """The self-healing harness gate: shard the nested HPC campaign
    with one injected worker kill and one injected hang; the healed
    sweep must merge cell-for-cell identical to the serial one. Returns
    the cell count (the gate raises on any divergence)."""
    from .scenarios_sweep import full_divergences

    cfg = NVMConfig(cache_bytes=1024 * 1024)
    kw = dict(workloads=SMOKE_HPC_WORKLOADS if smoke else HPC_WORKLOADS,
              strategies=HPC_STRATEGIES, plans=_nested_plans(smoke),
              cfg=cfg)
    serial = sweep(mode="measure", workers=1, **kw)
    chaotic = sweep(mode="measure", workers=2,
                    chaos={0: "kill", 1: "hang"},
                    shard_timeout=30 if smoke else 120,
                    journal=JOURNAL + ".chaos", **kw)
    div = full_divergences(chaotic, serial)
    if div:
        raise AssertionError(
            f"chaos-injected sharded sweep diverged from serial after "
            f"healing: {div[:3]}")
    return len(chaotic)


def run(smoke: bool = None, workers: int = None, mode: str = "measure",
        chaos: bool = False) -> List[Row]:
    from .scenarios_sweep import resolve_sweep_env

    smoke, workers = resolve_sweep_env(smoke, workers)
    all_cells = []
    census: Dict[Tuple, Counter] = {}
    resilience: Dict[Tuple, Counter] = {}
    matrices = []
    for campaign, kw in _campaign_sweeps(smoke):
        cells = sweep(mode=mode, workers=workers, journal=JOURNAL, **kw)
        check_fault_gates(campaign, kw, cells, workers)
        matrices.append({
            "campaign": campaign,
            "workloads": [[w, p] for w, p in kw["workloads"]],
            "strategies": list(kw["strategies"]),
            "plans": [p.describe() for p in kw["plans"]],
        })
        for c in cells:
            all_cells.append((campaign, c))
            if c.crash_step is None:
                continue
            key = (campaign, c.workload, c.strategy)
            census.setdefault(key, Counter())[c.correctness_class] += 1
            r = resilience.setdefault(key, Counter())
            r["attempts"] += int(c.info.get("recovery_attempts") or 0)
            r["nested_crashes"] += int(c.info.get("nested_crashes") or 0)
            r["fault_words"] += int(c.info.get("fault_words_injected") or 0)

    rows = []
    for key in sorted(census):
        campaign, wl, strat = key
        cls = census[key]
        res = resilience[key]
        total = sum(cls.values())
        prefix = f"fig_faults/{campaign}/{wl}/{strat}"
        rows.append(Row(f"{prefix}/cells", total,
                        " ".join(f"{k}={v}" for k, v in sorted(cls.items()))))
        if campaign == "nested":
            rows.append(Row(
                f"{prefix}/idempotent_fraction",
                cls.get("recovery_idempotent", 0)
                / max(1, sum(v for k, v in cls.items()
                             if k.startswith("recovery_"))),
                f"diverged={cls.get('recovery_diverged', 0)} "
                f"re-crashes={res['nested_crashes']} "
                f"attempts={res['attempts']}"))
        else:
            rows.append(Row(
                f"{prefix}/silent_cells", cls.get("fault_silent", 0),
                f"detected={cls.get('fault_detected', 0)} "
                f"words_injected={res['fault_words']}"))

    chaos_cells = None
    if chaos:
        chaos_cells = check_chaos_gate(smoke)
        rows.append(Row("fig_faults/chaos/cells", chaos_cells,
                        "kill+hang injected; healed sweep == serial"))

    write_json(BENCH_JSON, {
        "schema": "repro.scenarios.faults/v1",
        "smoke": bool(smoke),
        "matrices": matrices,
        "cells": [dict(campaign=camp, **c.to_json_dict())
                  for camp, c in all_cells],
        "census": [
            {"campaign": k[0], "workload": k[1], "strategy": k[2],
             "classes": dict(census[k]), **dict(resilience[k])}
            for k in sorted(census)],
        "chaos_gate_cells": chaos_cells,
    })
    rows.append(Row("fig_faults/summary/cells", len(all_cells),
                    f"artifact={BENCH_JSON}"))
    return rows


def main(argv=None) -> None:
    """``dense_figure_cli`` plus the ``--chaos`` leg (the self-healing
    harness gate is opt-in: it re-runs the nested campaign twice)."""
    import argparse

    from repro.launch.compile_cache import enable_compile_cache

    from .common import emit
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI size axis (gates run at every size)")
    ap.add_argument("--workers", type=int, default=None,
                    help="processes for the sweep "
                         "(default: REPRO_SWEEP_WORKERS or 2)")
    ap.add_argument("--mode", default="measure",
                    choices=["measure", "batched"],
                    help="cell evaluation mode (default: measure; fault "
                         "cells always evaluate per-cell)")
    ap.add_argument("--chaos", action="store_true",
                    help="also run the chaos gate: sharded sweep with an "
                         "injected worker kill + hang must equal serial")
    args = ap.parse_args(argv)
    enable_compile_cache()
    emit(run(smoke=args.smoke or None, workers=args.workers,
             mode=args.mode, chaos=args.chaos),
         save_as=ARTIFACT)


if __name__ == "__main__":
    main()
