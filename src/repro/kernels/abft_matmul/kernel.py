"""Pallas TPU kernel: blocked MXU matmul with fused ABFT-checksum epilogue.

TPU adaptation of the paper's §III.C mechanism (see DESIGN.md §2): on
x86/NVM the algorithm computes the product and then *selectively flushes
checksum cache lines*; on TPU the idiomatic equivalent is to generate the
checksums in the matmul epilogue while the accumulator tile is still in
VMEM, so the checksums ride the same HBM write stream as the result tile
— zero extra passes over C.

Grid is (m/bm, n/bn, k/bk) with the contraction dimension innermost; a
float32 VMEM scratch accumulates partial products across the k blocks
(MXU-aligned 128x128x128 default tiles). At the last k step the epilogue
writes, per (i, j) tile:

  * the C tile itself (cast to the output dtype),
  * a (bm, 1) row partial sum    -> row_partials[j, i*bm:(i+1)*bm, 0]
  * a (1, bn) column partial sum -> col_partials[i, 0, j*bn:(j+1)*bn]

The partials carry a leading per-tile axis that the block specs squeeze
away, so every block's last two dimensions are either multiples of
(8, 128) or the whole array dimension (the 1-wide axis) — the Mosaic
tiling rule. The tiny cross-tile reductions (summing partials over the
tile axis) happen in ops.py as jnp ops — XLA fuses them, and keeping
the kernel free of cross-tile accumulation avoids revisit-ordering
hazards in the Mosaic pipeline.

VMEM budget at the default 128-tile: a(64KB f32) + b(64KB) + acc(64KB) +
c(64KB) + partials(~1KB) ≈ 256KB double-buffered ≈ 512KB — comfortably
inside the 16MB/core VMEM of v5e, leaving room for the pipeline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["abft_matmul_pallas", "DEFAULT_BM", "DEFAULT_BN", "DEFAULT_BK"]

# MXU-native tile sizes (v5e systolic array is 128x128)
DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128


def _abft_mm_kernel(a_ref, b_ref, c_ref, rowp_ref, colp_ref, acc_ref):
    """One (i, j, kk) grid step."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # f32 operands need HIGHEST to keep f32 products on the MXU (the
    # default rounds them to bf16: 1.4e-2 of max |C_f| off a float32
    # reference at 4096^2 on v5e); Mosaic refuses it for bf16 operands,
    # whose products are exact anyway
    f32 = a_ref.dtype == jnp.float32
    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=acc_ref.dtype,
        precision=jax.lax.Precision.HIGHEST if f32 else None,
    )

    @pl.when(kk == pl.num_programs(2) - 1)
    def _epilogue():
        acc = acc_ref[...]
        c_ref[...] = acc.astype(c_ref.dtype)
        # fused ABFT epilogue: checksum partials leave VMEM with the tile
        rowp_ref[...] = jnp.sum(acc, axis=1, keepdims=True)
        colp_ref[...] = jnp.sum(acc, axis=0, keepdims=True)


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "out_dtype", "interpret"),
)
def abft_matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    out_dtype=None,
    interpret: bool = False,
):
    """C = a @ b with fused row/col checksum partials.

    a: (m, k), b: (k, n); m % bm == k % bk == n % bn == 0 (ops.py pads).
    Returns (C (m,n) out_dtype, row_partials (n/bn, m, 1) f32,
             col_partials (m/bm, 1, n) f32); the VMEM accumulator is f32.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, f"contraction mismatch {k} vs {k2}"
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"unpadded shapes ({m},{k},{n}) vs blocks ({bm},{bk},{bn})")
    out_dtype = out_dtype or a.dtype
    mi, nj = m // bm, n // bn

    return pl.pallas_call(
        _abft_mm_kernel,
        grid=(mi, nj, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((None, bm, 1), lambda i, j, kk: (j, i, 0)),
            pl.BlockSpec((None, 1, bn), lambda i, j, kk: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), out_dtype),
            jax.ShapeDtypeStruct((nj, m, 1), jnp.float32),
            jax.ShapeDtypeStruct((mi, 1, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
