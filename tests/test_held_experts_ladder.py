"""The held experts' ladder of buffer sizes at a small nemotron3-nano
size on the CPU: the layer at each rung against the plain reference of
the benchmark's configuration (outputs and gradients), what its
linearization keeps, and the ``buffer_rows`` counts of the ``moe``
counter group."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import training as T
from bench.harness import load_module
from bench.tests import tiny_nemotron as TN
from bench.tests.test_nemotron import (SEQ, close, model_config, reader,
                                       small_config)


@pytest.fixture(scope="module")
def reference():
    return load_module(os.path.join(TN.SOURCE_CONFIG, "reference.py"))


# the held experts at 512 tokens, top-3 of 16, 3 held (experts 4-6):
# buffers of 1,536, 768 and 384 rows
LADDER_TOKENS = 512
# (correction bias of the held experts, the rung it puts the held rows
# in): all of them (1,536), 565 rows, the drawn bias (342) and none
RUNG_BIASES = {"toward_held": (10.0, 0), "middle": (0.03, 1),
               "drawn": (None, 2), "away": (-10.0, 2)}


def ladder_case(reference, bias):
    from repro.models import moe
    cfg = small_config(experts_held=3, compute_dtype="float32")
    m = model_config(cfg)
    p = T.make_weights(reference, cfg, 5)["blocks"][1]["moe"]
    p = {k: p[k] for k in ("router", "bias", "w_up", "w_down")}
    value, rung = RUNG_BIASES[bias]
    if value is not None:
        p["bias"] = jnp.zeros(16).at[4:7].set(value)
    x = jax.random.normal(jax.random.PRNGKey(6), (LADDER_TOKENS, 64))
    ladder = moe.buffer_ladder(LADDER_TOKENS, m.experts_per_token, 3, 16)
    return cfg, m, p, x, ladder, rung


@pytest.mark.parametrize("bias", sorted(RUNG_BIASES))
def test_held_experts_against_the_reference_at_each_rung(reference, bias):
    """The held experts' part, and its gradients with respect to x, the
    router, w_up and w_down, against the reference (every held expert on
    every token), float32, under the step's ``dots`` checkpoint policy,
    with the held rows in each rung of the ladder. Tolerance: round-off
    of the grouped products against the dense ones."""
    from repro.models import moe
    cfg, m, p, x, ladder, rung = ladder_case(reference, bias)
    d = reference.dims(cfg)
    cot = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    dots = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    layer = jax.checkpoint(lambda p, x: moe.routed_experts(m, p, x)[0],
                           policy=dots)
    with jax.default_matmul_precision("highest"):
        rows = moe.routed_experts(m, p, x)[1]["rows"]
        got, got_grads = jax.value_and_grad(
            lambda p, x: jnp.sum(layer(p, x) * cot), argnums=(0, 1))(p, x)
        want, want_grads = jax.value_and_grad(
            lambda p, x: jnp.sum(reference.held_experts(d, p, x) * cot),
            argnums=(0, 1))(p, x)
        out, ref_out = layer(p, x), reference.held_experts(d, p, x)
    assert moe.buffer_rung(ladder, int(jnp.sum(rows))) == ladder[rung]
    np.testing.assert_allclose(
        out, ref_out, rtol=0,
        atol=1e-5 * max(float(jnp.max(jnp.abs(ref_out))), 1.0))
    assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-6)
    close(got_grads, want_grads, 1e-4)
    if bias == "away":
        # no held row: no routed part, and the experts learn nothing
        assert int(jnp.sum(rows)) == 0
        assert not np.any(np.asarray(out))
        for k in ("w_up", "w_down"):
            assert not np.any(np.asarray(got_grads[0][k])), k


def test_linearized_layer_keeps_only_the_rung_it_takes(reference):
    """What the layer's linearization keeps for the backward pass: no
    array of a buffer it did not take (a switch differentiated as it
    stands keeps every buffer's), and of the largest only the
    assignments' order and weights (vectors), no buffer of rows."""
    from repro.models import moe
    _, m, p, x, ladder, rung = ladder_case(reference, "middle")
    _, lin = jax.linearize(lambda p, x: moe.routed_experts(m, p, x)[0],
                           p, x)
    kept = [a.shape for a in jax.tree.leaves(lin)]
    untaken = set(ladder[1:]) - {ladder[rung]}
    assert LADDER_TOKENS not in ladder and untaken
    for shape in kept:
        assert not shape or shape[0] not in untaken, shape
        if shape and shape[0] == ladder[0]:
            assert len(shape) == 1, shape


def test_count_routing_adds_the_buffer_rows(monkeypatch):
    """``("buffer_rows", layer)`` is the sum over steps of the ladder's
    rung for the layer's held rows; the load ratio reads the ``rows``
    keys alone."""
    from repro import tracing
    from repro.launch.train import count_routing
    from repro.models import moe
    m = model_config(small_config())
    tokens = 2 * 4096
    ladder = moe.buffer_ladder(tokens, m.experts_per_token, 4, 16)
    assert len(ladder) > 1
    steps = [np.array([[900, 1000, 1100, 1200], [0, 0, 0, 0],
                       [3000, 3000, 3000, 3000]]),
             np.array([[10, 20, 30, 40], [5000, 2000, 10, 0],
                       [0, 0, 0, 1]])]
    tracing.reset()
    for rows in steps:
        count_routing({"moe_rows": rows, "moe_overflow": np.zeros(3, int)},
                      m, tokens)
    group = dict(tracing.counters()["moe"])
    for layer in range(3):
        assert group[("buffer_rows", layer)] == sum(
            moe.buffer_rung(ladder, int(r[layer].sum())) for r in steps)
        assert group[("overflow", layer)] == 0
    total = sum(steps)
    assert reader("expert_load_ratio")({}) == pytest.approx(
        total.max() / total.mean())
    without = {k: n for k, n in group.items() if k[0] != "buffer_rows"}
    monkeypatch.setattr(tracing, "counters", lambda: {"moe": without})
    assert reader("expert_load_ratio")({}) == pytest.approx(
        total.max() / total.mean())


def test_trainer_counts_the_buffer_rows(tmp_path):
    """``("buffer_rows", layer)`` from the trainer is the sum of the
    ladder's rung for each step's held rows, as the step returned them."""
    from repro import tracing
    from repro.configs.base import TrainConfig
    from repro.launch.train import ADCCTrainer
    from repro.models import moe
    cfg = small_config()
    trainer = ADCCTrainer(model_config(cfg), TrainConfig(**cfg["train"]),
                          str(tmp_path), batch=2, seq=SEQ, slot_every=64)
    seen = []
    step = trainer.step_fn

    def tap(*args):
        out = step(*args)
        seen.append(np.asarray(out[3]["moe_rows"]))
        return out

    trainer.step_fn = tap
    tracing.reset()
    trainer.run(3, log_every=0)
    group = tracing.counters()["moe"]
    assert len(seen) == 3
    ladder = moe.buffer_ladder(2 * SEQ, 3, 4, 16)
    for layer in range(3):
        assert group[("buffer_rows", layer)] == sum(
            moe.buffer_rung(ladder, int(rows[layer].sum())) for rows in seen)
