"""Per-architecture smoke tests (assignment requirement): instantiate a
REDUCED config of the same family, run one forward/train step on CPU,
assert output shapes + no NaNs; decode smoke for decoder archs."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch.specs import make_batch
from repro.models.registry import build_model, get_config, list_archs

ARCHS = list_archs()


def _reduced_api(arch):
    cfg = get_config(arch).reduced()
    return build_model(cfg), cfg


@pytest.mark.parametrize("arch", ARCHS)
class TestArchSmoke:
    def test_forward_and_train_step(self, arch):
        api, cfg = _reduced_api(arch)
        params, axes = api.init(jax.random.PRNGKey(0))
        B, S = 2, 32
        batch = make_batch(cfg, B, S, jax.random.PRNGKey(1))

        logits = api.forward(params, batch)
        exp_seq = S
        assert logits.shape == (B, exp_seq, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

        # one SGD train step: loss + grads finite, params change
        loss, grads = jax.value_and_grad(
            lambda p: api.loss_fn(p, batch))(params)
        assert bool(jnp.isfinite(loss))
        flat = jax.tree.leaves(grads)
        assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
                   for g in flat)
        new_params = jax.tree.map(lambda p, g: p - 1e-3 * g.astype(p.dtype),
                                  params, grads)
        loss2 = api.loss_fn(new_params, batch)
        assert bool(jnp.isfinite(loss2))

    def test_param_axes_cover_params(self, arch):
        """Every param leaf must carry a logical-axes tuple of equal rank
        (the sharding layer depends on this)."""
        api, cfg = _reduced_api(arch)
        shapes, axes = api.abstract_init(jax.random.PRNGKey(0))
        leaves_p, tdef_p = jax.tree.flatten(shapes)
        is_axes = lambda t: (isinstance(t, tuple)
                             and all(isinstance(s, str) for s in t))
        leaves_a, tdef_a = jax.tree.flatten(axes, is_leaf=is_axes)
        assert len(leaves_p) == len(leaves_a)
        for p, a in zip(leaves_p, leaves_a):
            assert len(a) == len(p.shape), (a, p.shape)

    def test_decode_step(self, arch):
        api, cfg = _reduced_api(arch)
        if not cfg.is_decoder:
            # encoder-only archs (non-causal, e.g. hubert) have no
            # autoregressive path BY CONTRACT: the registry must expose
            # neither a decode step nor a KV cache for them. Asserting
            # that replaces the old bare pytest.skip — the case now
            # tests the registry's encoder/decoder surface instead of
            # reporting a perennial skip.
            assert api.decode_step is None and api.init_cache is None
            return
        assert api.decode_step is not None and api.init_cache is not None
        params, _ = api.init(jax.random.PRNGKey(0))
        B, max_len = 2, 16
        cache, _ = api.init_cache(B, max_len)
        tok = jnp.zeros((B, 1), jnp.int32)
        for pos in range(3):
            logits, cache = api.decode_step(params, cache, tok, pos)
            assert logits.shape == (B, 1, cfg.vocab_size)
            assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
            tok = jnp.argmax(logits[:, :, :], axis=-1).astype(jnp.int32)


def test_all_ten_archs_registered():
    # the ten assigned architectures and nemotron3-nano-30b-a3b
    assert len(ARCHS) == 11


@pytest.mark.parametrize("arch,expected_b", [
    ("granite-8b", 8.0), ("llama3-8b", 8.0), ("phi4-mini-3.8b", 3.8),
    ("deepseek-v2-lite-16b", 16.0), ("kimi-k2-1t-a32b", 1000.0),
    ("hubert-xlarge", 1.0), ("qwen2-vl-2b", 1.5), ("zamba2-1.2b", 1.2),
    ("mamba2-130m", 0.13), ("granite-3-8b", 8.0),
    ("nemotron3-nano-30b-a3b", 31.6),
])
def test_param_counts_match_published(arch, expected_b):
    n = get_config(arch).param_count() / 1e9
    assert 0.7 * expected_b <= n <= 1.35 * expected_b, (arch, n)


def test_decode_matches_forward_dense():
    """Teacher-forced decode must reproduce the forward logits exactly."""
    api, cfg = _reduced_api("llama3-8b")
    params, _ = api.init(jax.random.PRNGKey(0))
    B, S = 2, 8
    batch = make_batch(cfg, B, S, jax.random.PRNGKey(1))
    ref = api.forward(params, batch)
    cache, _ = api.init_cache(B, S)
    outs = []
    for t in range(S):
        lg, cache = api.decode_step(params, cache,
                                    batch["tokens"][:, t:t + 1], t)
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    assert float(jnp.max(jnp.abs(dec - ref))) < 1e-4


def test_decode_matches_forward_ssm():
    api, cfg = _reduced_api("mamba2-130m")
    params, _ = api.init(jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = make_batch(cfg, B, S, jax.random.PRNGKey(1))
    ref = api.forward(params, batch)
    cache, _ = api.init_cache(B, S)
    outs = []
    for t in range(S):
        lg, cache = api.decode_step(params, cache,
                                    batch["tokens"][:, t:t + 1], t)
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    assert float(jnp.max(jnp.abs(dec - ref))) < 5e-2  # fp32 scan reorder


def test_decode_matches_forward_hybrid():
    api, cfg = _reduced_api("zamba2-1.2b")
    params, _ = api.init(jax.random.PRNGKey(0))
    B, S = 2, 8
    batch = make_batch(cfg, B, S, jax.random.PRNGKey(1))
    ref = api.forward(params, batch)
    cache, _ = api.init_cache(B, S)
    outs = []
    for t in range(S):
        lg, cache = api.decode_step(params, cache,
                                    batch["tokens"][:, t:t + 1], t)
        outs.append(lg)
    dec = jnp.concatenate(outs, axis=1)
    assert float(jnp.max(jnp.abs(dec - ref))) < 5e-2
