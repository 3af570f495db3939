"""jit'd wrapper: checksum verification via the tile-sums Pallas kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..abft_matmul.ops import on_tpu
from .kernel import tile_sums_pallas

__all__ = ["verify_checksums", "tile_sums"]


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _pick_block(dim: int, default: int = 128) -> int:
    for cand in (default, 64, 32, 16, 8):
        if dim >= cand:
            return cand
    return 8


@functools.partial(jax.jit, static_argnames=("interpret",))
def tile_sums(x: jax.Array, *, interpret: bool):
    """(row_sums (m,), col_sums (n,)) of x via one Pallas HBM pass."""
    m, n = x.shape
    bm, bn = _pick_block(m), _pick_block(n)
    mp, np_ = _round_up(m, bm), _round_up(n, bn)
    x_p = jnp.pad(x, ((0, mp - m), (0, np_ - n)))
    rowp, colp = tile_sums_pallas(x_p, bm=bm, bn=bn, interpret=interpret)
    return jnp.sum(rowp[:, :m, 0], axis=0), jnp.sum(colp[:, 0, :n], axis=0)


def verify_checksums(cf: jax.Array, rtol: float = 1e-6, atol: float = 1e-4,
                     *, interpret: bool | None = None):
    """Kernel-backed verdict for a full-checksum matrix cf (m+1, n+1).
    Returns (ok, row_resid (m,), col_resid (n,)) like ref.verify_ref."""
    if interpret is None:
        interpret = not on_tpu()
    data = cf[:-1, :-1]
    row_sums, col_sums = tile_sums(data, interpret=interpret)
    row_resid = cf[:-1, -1].astype(jnp.float32) - row_sums
    col_resid = cf[-1, :-1].astype(jnp.float32) - col_sums
    scale = jnp.maximum(jnp.max(jnp.abs(cf)).astype(jnp.float32), 1.0)
    tol = atol + rtol * scale
    ok = (jnp.max(jnp.abs(row_resid)) <= tol) & (jnp.max(jnp.abs(col_resid)) <= tol)
    return ok, row_resid, col_resid
