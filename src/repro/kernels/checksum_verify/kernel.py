"""Pallas TPU kernel: tiled checksum-consistency detection.

The recovery path's hot loop (paper §III.C "detecting where to restart")
is a full pass over a checksummed matrix computing row and column sums to
compare against the embedded checksums. This kernel computes per-tile
row/column partial sums in one HBM pass; ops.py reduces the partials and
forms the residuals against the checksum row/column.

Grid (m/bm, n/bn); each step reduces a (bm, bn) VMEM tile into a
(bm, 1) row partial and a (1, bn) column partial — pure VPU work, memory
bound by design (arithmetic intensity ~2 flops/byte), so the roofline
target is HBM bandwidth. As in the ABFT matmul epilogue, the partials
carry a leading per-tile axis that the block specs squeeze away, so
every block meets the Mosaic (8, 128) tiling rule.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["tile_sums_pallas"]


def _tile_sums_kernel(x_ref, rowp_ref, colp_ref):
    x = x_ref[...].astype(jnp.float32)
    rowp_ref[...] = jnp.sum(x, axis=1, keepdims=True)
    colp_ref[...] = jnp.sum(x, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def tile_sums_pallas(x: jax.Array, *, bm: int = 128, bn: int = 128,
                     interpret: bool = False):
    """Row/col partial sums of x (m, n) with m % bm == n % bn == 0.
    Returns (row_partials (n/bn, m, 1), col_partials (m/bm, 1, n)), both
    f32."""
    m, n = x.shape
    assert m % bm == 0 and n % bn == 0, f"unpadded ({m},{n}) vs ({bm},{bn})"
    mi, nj = m // bm, n // bn
    return pl.pallas_call(
        _tile_sums_kernel,
        grid=(mi, nj),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((None, bm, 1), lambda i, j: (j, i, 0)),
            pl.BlockSpec((None, 1, bn), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nj, m, 1), jnp.float32),
            jax.ShapeDtypeStruct((mi, 1, n), jnp.float32),
        ],
        interpret=interpret,
    )(x)
