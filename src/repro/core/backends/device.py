"""Device-resident forward pass: jax-jit cache transitions over the
vectorized backend's state.

``DeviceBackend`` lifts the NVM-emulation *forward pass* — the write
coalescing, dirty bitmaps/stamps, and traffic accounting that every
golden prefix pays per step — onto jit-compiled kernels
(:func:`repro.core.backends.batched.cache_op_update` /
:func:`queue_validity`). It subclasses :class:`VectorizedBackend` and
overrides exactly two inner loops:

* ``_op``: a span operation whose entry range is large and provably
  eviction-free (the streaming regime — CSR matvec rows, MC grids, KV
  value-log extents under an adequate cache) is computed as one fused
  device launch producing the new bitmaps/stamps, the miss mask, and
  the miss count; the host then commits the results, queue-appends in
  the reference order, and charges traffic once. The launch is
  *speculative*: nothing is mutated until the no-eviction precondition
  (``occupancy + misses * weight <= capacity``) is confirmed, so any op
  that could evict falls back to the parent's host path untouched —
  byte/stat-identity with :class:`VectorizedBackend` is by
  construction, not by reimplementation.
* ``_validity``: queue-slot validation for large single-region blocks
  (the eviction/compaction/crash-order scan) as one gather launch.

Everything else — batched eviction, flush, drain, ``crash(survival)``
line- and word-granularity torn paths, ``snapshot()/restore()``, media
faults via ``corrupt_image_words`` — is inherited unchanged, so the
fork ladder, snapshot tiering, and fault injection run on top of it
with the established cross-backend byte-identity contracts intact
(gated by tests/test_backend_equivalence.py).

The kernels are plain jnp under ``jax.enable_x64``: per the accelerator
guide, these transitions are memory-bound elementwise/gather ops that
XLA already fuses into single kernels — a hand-written Pallas grid
would add block-spec bookkeeping for no arithmetic win (unlike the
ABFT/CG launches in ``repro.kernels``, which are MXU-shaped). Shapes
are padded to powers of two so jit compiles log-many variants.

Below :data:`DeviceBackend.MIN_DEVICE_ENTRIES`, where dispatch overhead
dominates, every path falls back to the parent.

Worker-pool caveat: the first device op instantiates an XLA backend in
this process; forking after that deadlocks children's device math. The
sweep driver switches its pool to spawn-start whenever
``jax_runtime_live()`` reports a live runtime (see
``repro.scenarios.driver.sweep``).
"""

from __future__ import annotations

import numpy as np

from . import batched as _dev
from .vectorized import VectorizedBackend

__all__ = ["DeviceBackend"]


class DeviceBackend(VectorizedBackend):
    """Vectorized cache emulation with jit-compiled bulk transitions."""

    kind = "device"

    # smallest entry count routed to the device: below this the jit
    # dispatch overhead exceeds the fused-transition win (tests lower it
    # to force every span op through the device kernels)
    MIN_DEVICE_ENTRIES = 2048

    def _op(self, name: str, lo: int, hi: int, is_write: bool) -> None:
        r = self._regions[name]
        if hi <= lo:
            return
        e_lo = lo // r.epe
        e_hi = (hi - 1) // r.epe + 1
        m = e_hi - e_lo
        if m < self.MIN_DEVICE_ENTRIES:
            super()._op(name, lo, hi, is_write)
            return
        sl = slice(e_lo, e_hi)
        t0 = self._clock
        fifo = self.cfg.replacement == "fifo"
        new_p, new_d, new_s, miss, n_miss = _dev.cache_op_update(
            r.present[sl], r.dirty[sl], r.stamp[sl], t0, is_write, fifo)
        if self._weight_used + n_miss * r.w > self.capacity_lines:
            # eviction pressure: nothing mutated yet — the parent's
            # hit/miss-run walk with interleaved queue pops is the
            # reference-exact path
            super()._op(name, lo, hi, is_write)
            return
        self._clock = t0 + m
        r.present[sl] = new_p
        r.dirty[sl] = new_d
        r.stamp[sl] = new_s
        ents = np.arange(e_lo, e_hi, dtype=np.int64)
        stamps = t0 + np.arange(m, dtype=np.int64)
        if fifo:
            # FIFO hits keep their queue slot; only misses enqueue
            self._q_append(r.rid, ents[miss], stamps[miss])
        else:
            self._q_append(r.rid, ents, stamps)
        self._weight_used += n_miss * r.w
        self.store.stats.charge_batch(
            self.cfg, write_bytes=0,
            read_bytes=0 if is_write else n_miss * r.epe * r.itemsize,
            evict_lines=0)

    def _validity(self, rids: np.ndarray, ents: np.ndarray,
                  stamps: np.ndarray):
        n = rids.shape[0]
        if n < self.MIN_DEVICE_ENTRIES:
            return super()._validity(rids, ents, stamps)
        rid0 = int(rids[0])
        if not np.all(rids == rid0):
            return super()._validity(rids, ents, stamps)
        r = self._by_rid.get(rid0)
        if r is None:  # dropped region: every slot is stale
            return (np.zeros(n, dtype=bool), np.zeros(n, dtype=np.int64))
        return _dev.queue_validity(r.present, r.stamp, ents, stamps, r.w)
