"""Device-math layer for the batched sweep engine (``sweep(mode="batched")``).

The batched engine (repro.scenarios.batched_engine) evaluates every
crash cell of a (workload, strategy) pair from host-side snapshots; the
only per-cell work that is numerically heavy is integrity checking —
CG's invariant backward-scan (orthogonality + residual per candidate
iteration) and ABFT's per-chunk checksum verification. This module
lifts exactly that math onto jax: the engine stacks every (cell,
candidate) / (cell, chunk) crash-image row of a whole sweep matrix and
gets the error magnitudes back from a handful of jit launches.

Every launch here is plain XLA under a scoped ``jax.enable_x64``: the
screens need float64 (an f32 dot product carries ~1e-7 relative error,
the size of CG's orthogonality tolerance, so it could certify nothing)
and the KV checksums are uint64 SplitMix chains. XLA lowers both on
every backend, the TPU included; Mosaic (Pallas) has no 64-bit types,
so no launch here goes through a Pallas kernel.

Device results are used as a *screen*, not a verdict: accumulation
order on device differs from the host reference by a few ulps, so the
engine accepts a device verdict only outside a safety band around the
tolerance (certainly-ok / certainly-fail) and recomputes the borderline
sliver — NaN included — with the exact host code
(`repro.core.invariants`, `repro.core.abft`). That keeps batched cells
bit-identical to measure-mode cells while the overwhelming majority of
checks never touch the host path.

Shapes are padded to a few fixed sizes (powers of two up to the
``CHUNK_ELEMS`` budget) so jit compiles a handful of kernels per
problem size instead of one per batch. :data:`LAUNCHES` counts every
launch by kernel and by the platform its result landed on.
"""

from __future__ import annotations

import collections
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["jax_runtime_live", "LAUNCHES",
           "cg_invariant_errors", "mm_chunk_stats",
           "kv_row_checksums", "kv_value_match",
           "cache_op_update", "queue_validity",
           "CHUNK_ELEMS", "SPARSE_BLOCK_ROWS"]

# per-launch element budget: bounds device/host transfer buffers and
# keeps padded launch shapes to a handful of compiled variants
CHUNK_ELEMS = 1 << 25

# fixed CG launch width: every chunk is padded to this many rows so jit
# compiles exactly one shape per (n, nnz), however the caller's
# batch/wave sizes vary
SPARSE_BLOCK_ROWS = 256

# (kernel name, platform of its result) -> launches in this process
LAUNCHES: collections.Counter = collections.Counter()


def _count(kernel: str, out: jax.Array) -> None:
    LAUNCHES[(kernel, next(iter(out.devices())).platform)] += 1


def jax_runtime_live() -> bool:
    """Whether this process has already instantiated an XLA backend
    (device buffers, compilation threads, locks). Forking a process in
    that state deadlocks the children's device math — the sweep driver
    switches its worker pool to spawn-start when this is true."""
    try:
        from jax._src import xla_bridge
        return bool(xla_bridge._backends)
    except Exception:  # pragma: no cover - private-API drift
        return True  # conservative: assume live, pay the spawn cost


def _chunk_rows(total: int, elems_per_row: int) -> int:
    """Fixed launch row-count: the CHUNK_ELEMS budget, or the next power
    of two when the whole batch is smaller (so small batches reuse a
    log-many set of compiled shapes instead of one per batch size)."""
    cap = max(1, CHUNK_ELEMS // max(1, elems_per_row))
    if total >= cap:
        return cap
    return _pow2_rows(total)


def _pow2_rows(n: int) -> int:
    c = 1
    while c < n:
        c <<= 1
    return c


def _pad_rows(block: np.ndarray, rows: int) -> np.ndarray:
    if block.shape[0] >= rows:
        return block
    # np.zeros + slice assign: np.pad's generic path is several times
    # slower and this sits on the per-launch hot path
    out = np.zeros((rows,) + block.shape[1:], dtype=block.dtype)
    out[:block.shape[0]] = block
    return out


# ---------------------------------------------------------------------------
# CG invariant errors (Eq. 1 orthogonality, Eq. 2 residual)
# ---------------------------------------------------------------------------

@jax.jit
def _cg_errors_jit(P, Q, R, Z, b, vals, cols):
    # batched sparse matvec over the padded equal-width symmetrized
    # operator (vals/cols are (n, K) row slabs, zero-padded): pure
    # gather + multiply + reduce, O(nnz) work per candidate row and no
    # device scatter
    Sz = jnp.sum(Z[:, cols] * vals[None, :, :], axis=-1)
    pq = jnp.sum(P * Q, axis=1)
    denom = jnp.linalg.norm(P, axis=1) * jnp.linalg.norm(Q, axis=1) + 1e-300
    orth = jnp.abs(pq) / denom
    resid = jnp.linalg.norm(R - (b[None, :] - Sz), axis=1)
    rel = resid / (jnp.linalg.norm(b) + 1e-300)
    return orth, rel


def cg_invariant_errors(P: np.ndarray, Q: np.ndarray, R: np.ndarray,
                        Z: np.ndarray, b: np.ndarray,
                        vals: np.ndarray, cols: np.ndarray
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Batched CG invariant error magnitudes over candidate rows.

    P/Q/R/Z are (T, n) stacks of post-crash overlay rows — one row per
    (cell, candidate iteration) pair. ``vals``/``cols`` hold the
    symmetrized system matrix S = 0.5*(A + A^T) as (n, K) equal-width
    row slabs, rows zero-padded to the widest row (see
    :func:`~repro.scenarios.batched_engine._CGAdccEvaluator._operator`).
    Returns (orth_err (T,), resid_rel (T,)) as float64 numpy arrays:

      orth_err[t]  = |p.q| / (|p||q| + 1e-300)       (vs tol 1e-7)
      resid_rel[t] = ||r - (b - S z)|| / (||b|| + 1e-300)  (vs tol 1e-6)

    the exact quantities OrthogonalityInvariant / ResidualInvariant
    compare — up to device accumulation order, which is why callers
    apply a certainty band before trusting a verdict.
    """
    T, n = P.shape
    rows = min(SPARSE_BLOCK_ROWS, _chunk_rows(T, 4 * n))
    orth = np.empty(T, dtype=np.float64)
    rel = np.empty(T, dtype=np.float64)
    with jax.enable_x64(True):
        bj = jnp.asarray(np.asarray(b, dtype=np.float64))
        vj = jnp.asarray(np.asarray(vals, dtype=np.float64))
        cj = jnp.asarray(np.asarray(cols, dtype=np.int32))
        for lo in range(0, T, rows):
            hi = min(lo + rows, T)
            o, r = _cg_errors_jit(jnp.asarray(_pad_rows(P[lo:hi], rows)),
                                  jnp.asarray(_pad_rows(Q[lo:hi], rows)),
                                  jnp.asarray(_pad_rows(R[lo:hi], rows)),
                                  jnp.asarray(_pad_rows(Z[lo:hi], rows)),
                                  bj, vj, cj)
            _count("cg_errors", o)
            orth[lo:hi] = np.asarray(o)[:hi - lo]
            rel[lo:hi] = np.asarray(r)[:hi - lo]
    return orth, rel


# ---------------------------------------------------------------------------
# ABFT chunk statistics
# ---------------------------------------------------------------------------

@jax.jit
def _mm_stats_jit(V):
    data = V[:, :-1, :-1]
    rowmax = jnp.max(jnp.abs(V[:, :-1, -1] - jnp.sum(data, axis=2)), axis=1)
    colmax = jnp.max(jnp.abs(V[:, -1, :-1] - jnp.sum(data, axis=1)), axis=1)
    absmax = jnp.max(jnp.abs(V), axis=(1, 2))
    nonzero = jnp.any(V != 0, axis=(1, 2))
    return nonzero, absmax, rowmax, colmax


def mm_chunk_stats(V: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Batched ABFT checksum statistics over full-checksum matrices.

    V is a (B, m, m) stack of post-crash chunk images (m = n+1 with the
    checksum row/column in place) — one slab per (cell, examined chunk)
    pair. Returns per-slab

      nonzero  any element != 0 (exact on device)
      absmax   max |V| (exact on device — no accumulation)
      rowmax   max row-checksum residual |V[:-1,-1] - sum(data, axis=1)|
      colmax   max col-checksum residual |V[-1,:-1] - sum(data, axis=0)|

    matching ``repro.core.abft.residuals``/``verify`` up to device
    summation order (callers apply a certainty band on rowmax/colmax;
    nonzero and the tolerance derived from absmax are exact).
    """
    B, m, _ = V.shape
    rows = _chunk_rows(B, m * m)
    nonzero = np.empty(B, dtype=bool)
    absmax = np.empty(B, dtype=np.float64)
    rowmax = np.empty(B, dtype=np.float64)
    colmax = np.empty(B, dtype=np.float64)
    with jax.enable_x64(True):
        for lo in range(0, B, rows):
            hi = min(lo + rows, B)
            nz, am, rm, cm = _mm_stats_jit(
                jnp.asarray(_pad_rows(V[lo:hi], rows)))
            _count("mm_stats", rm)
            nonzero[lo:hi] = np.asarray(nz)[:hi - lo]
            absmax[lo:hi] = np.asarray(am)[:hi - lo]
            rowmax[lo:hi] = np.asarray(rm)[:hi - lo]
            colmax[lo:hi] = np.asarray(cm)[:hi - lo]
    return nonzero, absmax, rowmax, colmax


# ---------------------------------------------------------------------------
# KV integrity math (SplitMix64 mix-chain checksums, value-word verify)
# ---------------------------------------------------------------------------
#
# Unlike the float CG/ABFT screens above, everything here is uint64
# integer arithmetic with wraparound semantics — bit-exact on every XLA
# backend — so no certainty band is needed: a device verdict IS the
# host verdict. The batched KV evaluator still re-confirms
# device-flagged-bad rows with the exact host code (repro.scenarios.kv),
# because those rare verdicts are the ones that drive visible behavior
# (row drops, violation counts) and the re-check costs nothing.

_SM64_GAMMA = 0x9E3779B97F4A7C15
_SM64_MIX1 = 0xBF58476D1CE4E5B9
_SM64_MIX2 = 0x94D049BB133111EB
_KV_MIX_INIT = 0x243F6A8885A308D3
_KV_VALUE_SALT = 21  # key << 21 ^ seq, matching kv._value_words
_MASK63 = (1 << 63) - 1


def _j_splitmix(z):
    z = z + jnp.uint64(_SM64_GAMMA)
    z = (z ^ (z >> jnp.uint64(30))) * jnp.uint64(_SM64_MIX1)
    z = (z ^ (z >> jnp.uint64(27))) * jnp.uint64(_SM64_MIX2)
    return z ^ (z >> jnp.uint64(31))


@functools.partial(jax.jit, static_argnames=("width",))
def _kv_row_ck_jit(words, *, width):
    # order-sensitive chain: acc_{j+1} = splitmix(acc_j ^ w_j); the
    # width is static (7 for index rows, 15 for meta rows) so the
    # chain unrolls into a fixed op sequence per compiled shape
    acc = jnp.full(words.shape[0], _KV_MIX_INIT, dtype=jnp.uint64)
    for j in range(width):
        acc = _j_splitmix(acc ^ words[:, j])
    return acc & jnp.uint64(_MASK63)


@jax.jit
def _kv_value_match_jit(keys, seqs, got, nwords):
    base = _j_splitmix((keys << jnp.uint64(_KV_VALUE_SALT)) ^ seqs)
    offs = jnp.arange(got.shape[1], dtype=jnp.uint64)
    expect = _j_splitmix(base[:, None] + offs[None, :]) \
        & jnp.uint64(_MASK63)
    live = offs[None, :] < nwords[:, None]
    return jnp.all(jnp.where(live, got == expect, True), axis=1)


@functools.partial(jax.jit, static_argnames=("is_write", "fifo"))
def _cache_op_jit(present, dirty, stamp, t0, *, is_write, fifo):
    # bulk no-eviction cache-op transition (see cache_op_update)
    pos = jnp.arange(present.shape[0], dtype=jnp.int64)
    miss = ~present
    new_stamp = t0 + pos if not fifo else jnp.where(miss, t0 + pos, stamp)
    new_dirty = (jnp.ones_like(dirty) if is_write
                 else jnp.logical_and(dirty, present))
    return (jnp.ones_like(present), new_dirty, new_stamp, miss,
            jnp.sum(miss, dtype=jnp.int64))


@jax.jit
def _queue_validity_jit(present, stamp, entries, stamps, weight):
    valid = jnp.logical_and(present[entries], stamp[entries] == stamps)
    return valid, jnp.where(valid, weight, 0).astype(jnp.int64)


def _as_u64(a: np.ndarray) -> np.ndarray:
    # int64 -> uint64 by two's-complement reinterpretation (== & MASK64),
    # matching the scalar host code's `w & _MASK64` on python ints
    return np.ascontiguousarray(np.asarray(a)).astype(np.uint64)


def kv_row_checksums(words: np.ndarray) -> np.ndarray:
    """Batched order-sensitive 63-bit mix-chain checksum per row.

    ``words`` is an (N, K) int64/uint64 stack of row prefixes (K = 7 for
    KV index rows, 15 for meta rows). Returns the (N,) int64 checksums —
    the device counterpart of ``repro.scenarios.kv._mix_words``, exact
    (integer wraparound is bit-identical on device and host).
    """
    if len(words) == 0:
        return np.empty(0, dtype=np.int64)
    w = _as_u64(words).reshape(len(words), -1)
    N, K = w.shape
    rows = _pow2_rows(max(1, N))
    with jax.enable_x64(True):
        out = _kv_row_ck_jit(jnp.asarray(_pad_rows(w, rows)), width=K)
        _count("kv_row_checksums", out)
        return np.asarray(out)[:N].astype(np.int64)


def kv_value_match(keys: np.ndarray, seqs: np.ndarray, got: np.ndarray,
                   nwords: np.ndarray) -> np.ndarray:
    """Batched value-word verification for KV index rows.

    Row i matches when ``got[i, :nwords[i]]`` equals the deterministic
    value words of (key, seq) — the device counterpart of comparing
    against ``repro.scenarios.kv._value_words``. ``got`` is (N, W)
    zero-padded beyond each row's width; returns an (N,) bool array.
    Exact (pure uint64 math).
    """
    if len(keys) == 0:
        return np.empty(0, dtype=bool)
    k = _as_u64(keys)
    s = _as_u64(seqs)
    g = _as_u64(got).reshape(len(k), -1)
    nw = np.asarray(nwords, dtype=np.int64)
    rows = _pow2_rows(max(1, len(k)))
    with jax.enable_x64(True):
        out = _kv_value_match_jit(
            jnp.asarray(_pad_rows(k, rows)), jnp.asarray(_pad_rows(s, rows)),
            jnp.asarray(_pad_rows(g, rows)),
            jnp.asarray(_pad_rows(nw, rows)))
        _count("kv_value_match", out)
        return np.asarray(out)[:len(k)]


# ---------------------------------------------------------------------------
# DeviceBackend step kernels (forward-pass cache transitions)
# ---------------------------------------------------------------------------

def cache_op_update(present: np.ndarray, dirty: np.ndarray,
                    stamp: np.ndarray, t0: int, is_write: bool, fifo: bool
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                               np.ndarray, int]:
    """Bulk cache-state transition for one span op touching entries
    ``[e_lo, e_hi)`` when no eviction is needed (the streaming regime).

    Inputs are the per-entry slices of a region's present/dirty bitmaps
    and LRU stamps; ``t0`` is the op's base clock tick. Returns
    ``(new_present, new_dirty, new_stamp, miss, n_miss)`` — exactly the
    state `VectorizedBackend._op` produces for a no-eviction op:

      * every touched entry ends resident;
      * a write dirties all touched entries, a read preserves dirt on
        hits and leaves misses clean;
      * LRU restamps every entry with ``t0 + position``; FIFO restamps
        misses only (hits keep their insertion stamp);
      * ``n_miss`` misses were fetched (the caller charges read traffic
        and queue-appends accordingly).

    The caller must pre-check capacity and fall back to the host path
    when the op could evict. Shapes are padded to powers of two
    (pad lanes: present=True, dirty=False — hits that never miss) so
    jit compiles log-many variants.
    """
    m = len(present)
    rows = _pow2_rows(max(1, m))
    pad = rows - m
    p = np.concatenate([present, np.ones(pad, dtype=bool)]) if pad else present
    d = _pad_rows(np.ascontiguousarray(dirty), rows)
    st = _pad_rows(np.ascontiguousarray(stamp), rows)
    with jax.enable_x64(True):
        np_, nd, ns, miss, n_miss = _cache_op_jit(
            jnp.asarray(p), jnp.asarray(d), jnp.asarray(st),
            jnp.int64(t0), is_write=bool(is_write), fifo=bool(fifo))
        _count("cache_op_update", ns)
        return (np.asarray(np_)[:m], np.asarray(nd)[:m],
                np.asarray(ns)[:m], np.asarray(miss)[:m], int(n_miss))


def queue_validity(present: np.ndarray, stamp: np.ndarray,
                   entries: np.ndarray, stamps: np.ndarray,
                   weight: int) -> Tuple[np.ndarray, np.ndarray]:
    """Eviction-queue slot validation for a single-region window.

    A queue slot is live when its entry is still resident and its
    recorded stamp matches the entry's current stamp (stale LRU
    re-touch duplicates fail the stamp check). Returns ``(valid, wts)``
    with ``wts[i] = weight`` (the region's sector-line weight) on valid
    slots and 0 elsewhere — the single-rid core of
    ``VectorizedBackend._validity``. Pad lanes (entry 0 / stamp 0) are
    never valid: a resident entry always carries a stamp >= 1.
    """
    n = len(entries)
    rows = _pow2_rows(max(1, n))
    with jax.enable_x64(True):
        valid, wts = _queue_validity_jit(
            jnp.asarray(np.ascontiguousarray(present)),
            jnp.asarray(np.ascontiguousarray(stamp)),
            jnp.asarray(_pad_rows(np.ascontiguousarray(entries), rows)),
            jnp.asarray(_pad_rows(np.ascontiguousarray(stamps), rows)),
            jnp.int64(weight))
        _count("queue_validity", valid)
        return np.asarray(valid)[:n], np.asarray(wts)[:n]
