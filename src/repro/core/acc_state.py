"""ADCC for training state: the checksum ledger (DESIGN.md §2-3).

The paper flushes one cache line per iteration (the loop counter) and
reasons about everything else with algorithm invariants. The training
analogue persists a few-KB *ledger record* synchronously each step —

    {step, rng seed, data cursor, per-leaf f32 checksums of
     (params, opt state, applied updates), loss}

— while the heavy state goes to slots asynchronously with no fences
(core/slots.py). Two invariant levels at recovery, both paper-style:

1. **Ledger integrity** — the linearity chain
       cks_params[t] ≈ cks_params[t-1] + cks_updates[t]
   (optimizer updates are additive, so the per-tensor sums obey the same
   recurrence; paper Eq. 1/2 analogue: an internal relation that torn
   records cannot satisfy). Torn/partial tail records are discarded.

2. **Slot consistency** — a slot written at step t is accepted iff every
   leaf's recomputed f32 sum matches the ledger's record for step t
   (ABFT checksum verification, Eq. 6 analogue, at tensor granularity).

Records are single JSON lines; a torn append produces an unparsable or
chain-breaking tail line, which recovery skips — by construction the
ledger needs no fsync ordering with the slots. A record's loss and
checksums come to the host in one transfer (``fetch_record_values``).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["LedgerRecord", "ChecksumLedger", "fetch_record_values",
           "verify_state_against_record"]

# the checksum groups of a record, in the order the record vector holds them
_CHECKSUM_GROUPS = ("params", "opt", "updates")


@jax.jit
def _record_vector(loss, cks, counts):
    """The loss, then every checksum in leaf order of each group, then
    every count flattened, as one f32 vector on the device."""
    leaves = [loss] + [x for g in _CHECKSUM_GROUPS
                       for x in jax.tree.leaves(cks[g])]
    vec = jnp.stack([jnp.asarray(x, jnp.float32) for x in leaves])
    if not counts:
        return vec
    return jnp.concatenate([vec] + [jnp.ravel(c).astype(jnp.float32)
                                    for c in jax.tree.leaves(counts)])


def fetch_record_values(loss, cks, counts=None) -> Dict[str, object]:
    """A record's ``loss``, ``cks_params``, ``cks_opt`` and
    ``cks_updates`` from the step's f32 scalars, brought to the host in
    one transfer. Each value is the scalar widened to a Python float,
    as ``float`` of it gives. ``counts``, a dict of small integer arrays
    the step reports beside its loss (the routing counts), rides in the
    same transfer and comes back under ``"counts"`` as int64 arrays of
    their shapes; it is no part of the record."""
    values = jax.device_get(_record_vector(loss, cks, counts or {}))
    out: Dict[str, object] = {"loss": float(values[0])}
    at = 1
    for g in _CHECKSUM_GROUPS:
        n = len(jax.tree.leaves(cks[g]))
        out["cks_" + g] = values[at:at + n].tolist()
        at += n
    if counts:
        got = {}
        for k, c in sorted(counts.items()):   # the leaf order of a dict
            got[k] = np.rint(values[at:at + c.size]).astype(
                np.int64).reshape(c.shape)
            at += c.size
        out["counts"] = got
    return out


@dataclasses.dataclass
class LedgerRecord:
    step: int
    rng_seed: int
    cursor: List[int]
    cks_params: List[float]
    cks_opt: List[float]
    cks_updates: List[float]
    loss: float

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), separators=(",", ":"))

    @classmethod
    def from_json(cls, line: str) -> "LedgerRecord":
        return cls(**json.loads(line))


class ChecksumLedger:
    """Append-only per-step ledger with linearity-chain validation."""

    # |sum(p_t) - (sum(p_{t-1}) + sum(u_t))| <= CHAIN_RTOL * scale
    CHAIN_RTOL = 1e-3
    SLOT_RTOL = 1e-4
    SLOT_ATOL = 1e-2

    def __init__(self, path: str):
        self.path = path
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._fh = None

    # -- write side -----------------------------------------------------------
    def append(self, rec: LedgerRecord) -> None:
        if self._fh is None:
            self._fh = open(self.path, "a", buffering=1)
        self._fh.write(rec.to_json() + "\n")
        self._fh.flush()
        os.fsync(self._fh.fileno())  # the "CLFLUSH": a few KB, synchronous

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    # -- read/verify side -----------------------------------------------------
    def read_all(self) -> List[LedgerRecord]:
        if not os.path.exists(self.path):
            return []
        out = []
        with open(self.path) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(LedgerRecord.from_json(line))
                except (json.JSONDecodeError, TypeError, KeyError):
                    break  # torn tail: discard the rest
        return out

    def validated_records(self) -> List[LedgerRecord]:
        """Drop any suffix that breaks the linearity chain (invariant 1)."""
        recs = self.read_all()
        good: List[LedgerRecord] = []
        for rec in recs:
            if good and rec.step == good[-1].step + 1 \
                    and len(rec.cks_params) == len(good[-1].cks_params):
                prev = np.asarray(good[-1].cks_params, np.float64)
                upd = np.asarray(rec.cks_updates, np.float64)
                cur = np.asarray(rec.cks_params, np.float64)
                scale = np.maximum(np.abs(cur), 1.0)
                if np.any(np.abs(cur - (prev + upd)) > self.CHAIN_RTOL * scale):
                    break  # chain broken: discard this record and the rest
            elif good and rec.step != good[-1].step + 1:
                break
            good.append(rec)
        return good

    def record_for_step(self, step: int) -> Optional[LedgerRecord]:
        for rec in reversed(self.validated_records()):
            if rec.step == step:
                return rec
        return None


def verify_state_against_record(params, opt_state, rec: LedgerRecord,
                                rtol: float = None, atol: float = None
                                ) -> Tuple[bool, int]:
    """Invariant 2: recompute per-leaf sums and compare with the ledger.
    Returns (ok, number of mismatching leaves)."""
    import jax
    import jax.numpy as jnp
    rtol = rtol if rtol is not None else ChecksumLedger.SLOT_RTOL
    atol = atol if atol is not None else ChecksumLedger.SLOT_ATOL

    def sums(tree):
        return [float(jnp.sum(jnp.asarray(x).astype(jnp.float32)))
                for x in jax.tree.leaves(tree)]

    got = np.asarray(sums(params) + sums(opt_state), np.float64)
    want = np.asarray(rec.cks_params + rec.cks_opt, np.float64)
    if got.shape != want.shape:
        return False, max(len(got), len(want))
    tol = atol + rtol * np.maximum(np.abs(want), 1.0)
    bad = int(np.sum(np.abs(got - want) > tol))
    return bad == 0, bad
