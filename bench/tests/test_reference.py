"""The plain reference against the system's model, at a small size on the
CPU, both in float32: the same loss and gradients from the same weights
and tokens (the reference steps the state recurrence one position at a
time, the model runs the chunked SSD form)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import BENCH_DIR, load_module

CONFIG_DIR = os.path.join(BENCH_DIR, "configs", "mamba2-130m")
# two of the reference's scan blocks
SEQ = 128


def small_config(**model):
    with open(os.path.join(CONFIG_DIR, "config.json")) as fh:
        cfg = json.load(fh)
    cfg["model"].update(n_layers=2, d_model=64, vocab_size=300, ssm_state=16,
                        ssm_head_dim=16, ssm_chunk=16, **model)
    cfg["shape"] = {"batch": 2, "seq": SEQ}
    return cfg


@pytest.fixture(scope="module")
def reference():
    return load_module(os.path.join(CONFIG_DIR, "reference.py"))


def program_loss_and_grad(cfg, params, tokens, labels):
    from repro.configs.base import ModelConfig
    from repro.models import ssm_lm
    model = ModelConfig(**cfg["model"])
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: ssm_lm.loss_fn(model, p, {"tokens": tokens,
                                                "labels": labels}))(params)


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 17])
def test_reference_matches_the_model_in_float32(reference, seed):
    cfg = small_config(compute_dtype="float32")
    from bench.training import BatchSource, make_weights
    params = make_weights(reference, cfg, seed)
    b = BatchSource(seed, 2, SEQ, 300).tokens(0)
    tokens, labels = jnp.asarray(b["tokens"]), jnp.asarray(b["labels"])
    loss, grads = reference.loss_and_grad(cfg, params, tokens, labels)
    want_loss, want_grads = program_loss_and_grad(cfg, params, tokens, labels)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(w)))
        err = float(jnp.max(jnp.abs(g - w)))
        assert err <= 1e-4 * scale + 1e-9, (jax.tree_util.keystr(path), err,
                                            scale)


def test_weights_have_the_models_layout(reference):
    from repro.configs.base import ModelConfig
    from repro.models import ssm_lm
    cfg = small_config()
    want, _ = ssm_lm.abstract_init(ModelConfig(**cfg["model"]),
                                   jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: reference.init_params(cfg, k),
                         jax.ShapeDtypeStruct((2,), jnp.uint32))
    shape = lambda t: jax.tree.map(lambda x: (x.shape, x.dtype), t)
    assert shape(got) == shape(want)


def test_control_rounds_projection_operands(reference):
    cfg = small_config()
    from bench.training import BatchSource, make_weights
    params = make_weights(reference, cfg, 5)
    b = BatchSource(5, 2, 32, 300).tokens(0)
    args = (jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
    exact, _ = reference.loss_and_grad(cfg, params, *args)
    low, _ = reference.loss_and_grad(cfg, params, *args,
                                     operand_dtype=jnp.float8_e4m3fn)
    assert float(low) != float(exact)
    assert abs(float(low) - float(exact)) < 0.05 * float(exact)


def test_flops_per_token_of_the_published_shapes():
    with open(os.path.join(CONFIG_DIR, "config.json")) as fh:
        cfg = json.load(fh)
    flops = load_module(os.path.join(CONFIG_DIR, "flops.py"))
    D, E, N, H, P, Q, V, L = 768, 1536, 128, 24, 64, 256, 50277, 24
    per_layer = (D * (2 * E + 2 * N + H) + E * D + 4 * (E + 2 * N)
                 + Q * N + Q * H * P + 2 * H * P * N)
    assert flops.train_flops_per_token(cfg) == 6.0 * (L * per_layer + D * V)
    assert 0.8e9 < flops.train_flops_per_token(cfg) < 1.0e9
    np.testing.assert_allclose(
        flops.train_flops_per_token(small_config()) / 6,
        2 * (64 * (2 * 128 + 32 + 8) + 128 * 64 + 4 * 160 + 16 * 16
             + 16 * 8 * 16 + 2 * 8 * 16 * 16) + 64 * 300)
