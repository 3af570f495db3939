"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode accepts: block shapes that
break the (8, 128) tiling rule, 64-bit types inside a Mosaic kernel,
programs that overflow a chip's memory. These tests compile, at real
widths, the Pallas kernels, nemotron3-nano-30b-a3b's splash attention and
grouped expert products, and the batched sweep's device launches at
the sizes of the figures that drive them (fig_torn CG n=2048 and MM
n=64, fig_kv's KV rows). Nothing runs.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
test worker imports this file. The persistent compilation cache is off
around these compiles (a described-device entry cannot be read back),
and x64 is on only around the 64-bit launches.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backends import batched
from repro.kernels.abft_matmul.kernel import abft_matmul_pallas
from repro.kernels.checksum_verify.kernel import tile_sums_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_abft_matmul_pallas_1024(one_chip, dtype):
    a = _spec(one_chip, (1024, 1024), dtype)
    compiled = _compile(abft_matmul_pallas, a, a)
    assert "tpu_custom_call" in compiled.as_text()


def test_tile_sums_pallas_1024(one_chip):
    x = _spec(one_chip, (1024, 1024), jnp.float32)
    compiled = _compile(tile_sums_pallas, x)
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_pallas_gqa4(one_chip):
    q = _spec(one_chip, (32, 2048, 128), jnp.bfloat16)
    kv = _spec(one_chip, (8, 2048, 128), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v: flash_attention_pallas(q, k, v, groups=4), q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_splash_causal_training_gqa16_8k(one_chip):
    """nemotron3-nano-30b-a3b's attention, forward and backward: 32 query
    heads over 2 KV heads, head dim 128, 8,192 positions."""
    from repro.models.layers import splash_causal
    q = _spec(one_chip, (1, 8192, 32, 128), jnp.bfloat16)
    kv = _spec(one_chip, (1, 8192, 2, 128), jnp.bfloat16)
    loss = lambda q, k, v: jnp.sum(splash_causal(q, k, v).astype(jnp.float32))
    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv).as_text()
    for phase in ("fwd", "dq", "dkv"):
        assert f"splash_mqa_{phase}" in text, phase


def test_held_experts_grouped_products(one_chip):
    """nemotron3-nano-30b-a3b's held experts, forward and backward: 16,384
    tokens routed over 128 experts, 6 each, 8 held, widths 2688 and 1856;
    the grouped products lower to the TPU's ragged-dot kernel."""
    import dataclasses

    from repro.models import moe
    from repro.models.registry import get_config
    cfg = dataclasses.replace(get_config("nemotron3-nano-30b-a3b"),
                              experts_held=8)
    D, F = cfg.d_model, cfg.moe_d_ff
    p = {"router": _spec(one_chip, (D, 128), jnp.float32),
         "bias": _spec(one_chip, (128,), jnp.float32),
         "w_up": _spec(one_chip, (8, D, F), jnp.bfloat16),
         "w_down": _spec(one_chip, (8, F, D), jnp.bfloat16)}
    x = _spec(one_chip, (16384, D), jnp.bfloat16)
    loss = lambda p, x: jnp.sum(
        moe.routed_experts(cfg, p, x)[0].astype(jnp.float32))
    text = _compile(jax.grad(loss, argnums=(0, 1)), p, x).as_text()
    assert "tpu_custom_call" in text and "ragged" in text


def _fig_torn_cg_operator():
    from benchmarks.fig_torn import WORKLOADS
    from repro.core.nvm import NVMConfig
    from repro.scenarios.batched_engine import _CGAdccEvaluator
    from repro.scenarios.workloads import make_workload

    (name, params), = [w for w in WORKLOADS if w[0] == "cg"]
    wl = make_workload((name, params))
    wl.setup(NVMConfig(cache_bytes=1024 * 1024), "adcc")
    return params["n"], _CGAdccEvaluator(wl)._operator()


def _no_mosaic(compiled):
    # the 64-bit launches are XLA only: Mosaic has no 64-bit types
    assert "tpu_custom_call" not in compiled.as_text()


def test_cg_errors_launch_fig_torn_n2048(one_chip):
    n, (vals, cols) = _fig_torn_cg_operator()
    assert n == 2048
    rows = batched.SPARSE_BLOCK_ROWS
    with jax.enable_x64(True):
        row = _spec(one_chip, (rows, n), jnp.float64)
        args = (row, row, row, row, _spec(one_chip, (n,), jnp.float64),
                _spec(one_chip, vals.shape, jnp.float64),
                _spec(one_chip, cols.shape, jnp.int32))
        _no_mosaic(batched._cg_errors_jit.lower(*args).compile())


def test_mm_stats_launch_fig_torn(one_chip):
    from benchmarks.fig_torn import WORKLOADS

    (n,) = [p["n"] for w, p in WORKLOADS if w == "mm"]
    m = n + 1
    rows = batched._chunk_rows(4096, m * m)
    with jax.enable_x64(True):
        v = _spec(one_chip, (rows, m, m), jnp.float64)
        _no_mosaic(batched._mm_stats_jit.lower(v).compile())


@pytest.mark.parametrize("width", [7, 15], ids=["index", "meta"])
def test_kv_row_checksums_launch(one_chip, width):
    with jax.enable_x64(True):
        w = _spec(one_chip, (4096, width), jnp.uint64)
        _no_mosaic(batched._kv_row_ck_jit.lower(w, width=width).compile())


def test_kv_value_match_launch(one_chip):
    with jax.enable_x64(True):
        vec = _spec(one_chip, (2048,), jnp.uint64)
        compiled = batched._kv_value_match_jit.lower(
            vec, vec, _spec(one_chip, (2048, 24), jnp.uint64),
            _spec(one_chip, (2048,), jnp.int64)).compile()
        _no_mosaic(compiled)


@pytest.mark.parametrize("is_write,fifo", [(False, False), (True, True)],
                         ids=["read-lru", "write-fifo"])
def test_cache_op_launch(one_chip, is_write, fifo):
    m = 4096
    with jax.enable_x64(True):
        flags = _spec(one_chip, (m,), np.bool_)
        compiled = batched._cache_op_jit.lower(
            flags, flags, _spec(one_chip, (m,), jnp.int64),
            _spec(one_chip, (), jnp.int64),
            is_write=is_write, fifo=fifo).compile()
        _no_mosaic(compiled)


def test_queue_validity_launch(one_chip):
    with jax.enable_x64(True):
        ents = _spec(one_chip, (4096,), jnp.int64)
        compiled = batched._queue_validity_jit.lower(
            _spec(one_chip, (8192,), np.bool_),
            _spec(one_chip, (8192,), jnp.int64), ents, ents,
            _spec(one_chip, (), jnp.int64)).compile()
        _no_mosaic(compiled)
