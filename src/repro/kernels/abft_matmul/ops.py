"""jit'd public wrappers around the ABFT matmul Pallas kernel.

Handles non-tile-aligned shapes by zero-padding (zeros change neither the
product nor the checksums), picks interpret mode automatically off-TPU,
and assembles the paper's full-checksum matrix C_f when asked.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import DEFAULT_BK, DEFAULT_BM, DEFAULT_BN, abft_matmul_pallas

__all__ = ["abft_matmul", "abft_matmul_full", "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _pick_block(dim: int, default: int) -> int:
    """Largest hardware-friendly block not exceeding the (padded) dim.
    Keeps the lane dimension at 128 where possible and falls back to the
    8-sublane minimum for small matrices."""
    for cand in (default, 128, 64, 32, 16, 8):
        if cand <= default and dim >= cand:
            return cand
    return 8


@functools.partial(jax.jit, static_argnames=("interpret",))
def _abft_matmul_impl(a, b, *, interpret: bool):
    m, k = a.shape
    _, n = b.shape
    bm = _pick_block(m, DEFAULT_BM)
    bn = _pick_block(n, DEFAULT_BN)
    bk = _pick_block(k, DEFAULT_BK)
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    a_p = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    b_p = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    c_p, rowp, colp = abft_matmul_pallas(
        a_p, b_p, bm=bm, bn=bn, bk=bk, interpret=interpret)
    c = c_p[:m, :n]
    row_cs = jnp.sum(rowp[:, :m, 0], axis=0)   # (m,)  sum over j tiles
    col_cs = jnp.sum(colp[:, 0, :n], axis=0)   # (n,)  sum over i tiles
    return c, row_cs, col_cs


def abft_matmul(a: jax.Array, b: jax.Array, *, interpret: bool | None = None):
    """C = a @ b plus fused row/col checksums. Returns (C, row_cs, col_cs)."""
    if interpret is None:
        interpret = not on_tpu()
    return _abft_matmul_impl(a, b, interpret=interpret)


def abft_matmul_full(a: jax.Array, b: jax.Array, *,
                     interpret: bool | None = None) -> jax.Array:
    """The paper's C_f = A_c @ B_r as an (m+1, n+1) full-checksum matrix,
    produced without materializing the encoded inputs."""
    c, row_cs, col_cs = abft_matmul(a, b, interpret=interpret)
    total = jnp.sum(row_cs)[None]
    top = jnp.concatenate([c.astype(jnp.float32), row_cs[:, None]], axis=1)
    bottom = jnp.concatenate([col_cs, total])[None, :]
    return jnp.concatenate([top, bottom], axis=0)
