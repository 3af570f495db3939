"""Unit/property coverage for framework substrates added during the perf
work: grouped-GEMM MoE path, data pipeline resumability, loop-aware HLO
analyzer, schedules, sharding helpers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import ModelConfig, TrainConfig
from repro.data.pipeline import SyntheticPipeline
from repro.models import moe
from repro.optim.adamw import lr_schedule


class TestGroupedGemm:
    @settings(max_examples=15, deadline=None)
    @given(r=st.integers(8, 96), e=st.integers(1, 6), d=st.integers(4, 24),
           f=st.integers(4, 24), seed=st.integers(0, 100))
    def test_scan_grouped_matches_ragged(self, r, e, d, f, seed):
        rng = np.random.default_rng(seed)
        gs = rng.multinomial(r, np.ones(e) / e).astype(np.int32)
        x = jnp.asarray(rng.normal(size=(r, d)), jnp.float32)
        wg = jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32)
        wu = jnp.asarray(rng.normal(size=(e, d, f)), jnp.float32)
        wd = jnp.asarray(rng.normal(size=(e, f, d)), jnp.float32)
        ref = moe._local_expert_ffn_ragged(x, jnp.asarray(gs), wg, wu, wd)
        # block_factor large enough that no rows are dropped
        got = moe._local_expert_ffn(x, jnp.asarray(gs), wg, wu, wd,
                                    block_factor=float(e))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_capacity_drop_zeroes_overflow(self):
        rng = np.random.default_rng(0)
        e, d, f = 2, 8, 8
        gs = jnp.asarray([30, 2], jnp.int32)   # skewed group
        x = jnp.asarray(rng.normal(size=(32, d)), jnp.float32)
        w = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
        got = moe._local_expert_ffn(x, gs, w(e, d, f), w(e, d, f),
                                    w(e, f, d), block_factor=1.0)
        # cap = 16: rows 16..29 of group 0 are dropped -> exactly zero
        assert bool(jnp.all(got[16:30] == 0.0))
        assert bool(jnp.any(got[:16] != 0.0))


class TestPipeline:
    CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=128)

    def test_batch_pure_function_of_step(self):
        p1 = SyntheticPipeline(self.CFG, batch=4, seq=16, seed=3)
        p2 = SyntheticPipeline(self.CFG, batch=4, seq=16, seed=3)
        for _ in range(3):
            next(p2)
        assert np.array_equal(p1.batch_at(7)["tokens"],
                              p2.batch_at(7)["tokens"])

    def test_cursor_resume_replays_stream(self):
        p1 = SyntheticPipeline(self.CFG, batch=4, seq=16, seed=1)
        seen = [next(p1)["tokens"] for _ in range(5)]
        cursor = p1.cursor()
        p2 = SyntheticPipeline(self.CFG, batch=4, seq=16, seed=999)
        p2.restore(cursor)
        nxt = next(p2)
        expect = SyntheticPipeline(self.CFG, batch=4, seq=16, seed=1).batch_at(5)
        assert np.array_equal(nxt["tokens"], expect["tokens"])

    def test_labels_are_shifted_tokens(self):
        b = SyntheticPipeline(self.CFG, batch=2, seq=16, seed=0).batch_at(0)
        # labels[t] continues the same underlying stream as tokens[t+1]
        assert np.array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_different_seeds_differ(self):
        a = SyntheticPipeline(self.CFG, batch=2, seq=16, seed=0).batch_at(0)
        b = SyntheticPipeline(self.CFG, batch=2, seq=16, seed=1).batch_at(0)
        assert not np.array_equal(a["tokens"], b["tokens"])


class TestHloAnalyzer:
    def test_scan_trip_multiplication(self):
        import sys, os
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
        from benchmarks.hlo_analysis import analyze

        def make(L):
            def f(x, w):
                def body(h, _):
                    return h @ w, None
                h, _ = jax.lax.scan(body, x, None, length=L)
                return h
            return f

        x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        for L in (1, 3, 7):
            c = jax.jit(make(L)).lower(x, w).compile()
            costs = analyze(c.as_text())
            assert abs(costs.flops - 2 * 128 ** 3 * L) / (2 * 128 ** 3 * L) \
                < 1e-6, L

    def test_nested_scan(self):
        import sys, os
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
        from benchmarks.hlo_analysis import analyze

        def g(x, w):
            def outer(h, _):
                def inner(h2, _):
                    return h2 @ w, None
                h2, _ = jax.lax.scan(inner, h, None, length=3)
                return h2, None
            h, _ = jax.lax.scan(outer, x, None, length=5)
            return h

        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        c = jax.jit(g).lower(x, w).compile()
        costs = analyze(c.as_text())
        assert abs(costs.flops / (2 * 64 ** 3 * 15) - 1) < 1e-6


class TestSchedules:
    def test_warmup_then_decay(self):
        tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=10,
                           total_steps=100)
        lrs = [float(lr_schedule(tcfg, jnp.int32(s))) for s in range(100)]
        assert lrs[0] < lrs[5] < lrs[10]          # warmup rises
        assert lrs[10] == max(lrs)                 # peak at warmup end
        assert lrs[-1] < 0.2 * max(lrs)            # decays


class TestShardAct:
    def test_noop_without_mesh(self):
        from repro.models.layers import shard_act
        x = jnp.ones((4, 8, 16))
        assert shard_act(x, None) is x

    def test_applies_on_named_mesh(self):
        from repro.launch.mesh import single_device_mesh
        from repro.models.layers import shard_act
        mesh = single_device_mesh()
        x = jnp.ones((4, 8, 16))
        y = shard_act(x, mesh)
        assert y.shape == x.shape

    def test_skips_unshardable_batch(self):
        from repro.launch.mesh import single_device_mesh
        from repro.models.layers import shard_act
        mesh = single_device_mesh()
        x = jnp.ones((1, 8, 16))   # batch 1 still divisible by 1 -> applied
        assert shard_act(x, mesh).shape == x.shape


class TestCompileCache:
    """``enable_compile_cache``: JAX_COMPILATION_CACHE_DIR wins when set;
    otherwise the cache sits at the checkout's fixed ``.jax_cache``."""

    def test_unset_env_points_at_checkout(self, monkeypatch):
        import os

        from repro.launch.compile_cache import enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        was = jax.config.jax_compilation_cache_dir
        try:
            got = enable_compile_cache()
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", was)
        with open(os.path.join(root, ".gitignore")) as fh:
            assert ".jax_cache/" in fh.read().split()

    def test_set_env_is_left_to_jax_and_cache_lands_there(self, tmp_path):
        import os
        import subprocess
        import sys

        code = (
            "import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "got = enable_compile_cache()\n"
            "assert got == jax.config.jax_compilation_cache_dir, got\n"
            "print(got)\n"
            "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()\n")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
                   PYTHONPATH=os.path.join(root, "src"))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == str(tmp_path / "cc")
        assert os.listdir(tmp_path / "cc")
