"""nemotron3-nano-30b-a3b at a small size on the CPU: the plain reference
against the system's model (loss and gradients, the grouped gated
Mamba-2 block, the expert shares), the routing counts, the cell's two
per-layer readers, and a rehearsal of its cell through the training
driver."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import training as T
from bench.faults import FAULTS
from bench.harness import BENCH_DIR, load_module, metric_values
from bench.tests import tiny_nemotron as TN

CONFIG_DIR = TN.SOURCE_CONFIG
# four of the reference's scan blocks, eight SSD chunks of 16
SEQ = 64
SEEDS = [0, 2 ** 31 + 17]


def small_config(**model):
    cfg = TN.tiny_config()
    cfg["model"].update(model)
    cfg["shape"] = {"batch": 2, "seq": SEQ}
    return cfg


def model_config(cfg):
    from repro.configs.base import ModelConfig
    return ModelConfig(**cfg["model"])


@pytest.fixture(scope="module")
def reference():
    return load_module(os.path.join(CONFIG_DIR, "reference.py"))


def batch_of(seed, cfg):
    b = T.BatchSource(seed, 2, SEQ, cfg["model"]["vocab_size"]).tokens(0)
    return jnp.asarray(b["tokens"]), jnp.asarray(b["labels"])


def close(got, want, rtol):
    """Each leaf's worst gap within ``rtol`` of that leaf's largest
    magnitude."""
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(w)))
        err = float(jnp.max(jnp.abs(g - w)))
        assert err <= rtol * scale + 1e-9, (jax.tree_util.keystr(path), err,
                                            scale)


# ---------------------------------------------------------------------------
# the reference against the model
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_reference_matches_the_model_in_float32(reference, seed):
    """Loss and every gradient from the same weights and tokens, both in
    float32: the reference steps the recurrence per position, computes
    every held expert on every token and attention in query blocks; the
    model runs the chunked SSD, the dropless grouped products and the
    XLA attention. Tolerance: float32 round-off of two summation orders
    over 64 positions (the readings are some 2e-6 of a leaf's largest
    gradient)."""
    from repro.models.registry import build_model
    cfg = small_config(compute_dtype="float32")
    params = T.make_weights(reference, cfg, seed)
    tokens, labels = batch_of(seed, cfg)
    loss, grads = reference.loss_and_grad(cfg, params, tokens, labels)
    api = build_model(model_config(cfg))
    with jax.default_matmul_precision("highest"):
        (want, counts), want_grads = jax.value_and_grad(
            lambda p: api.loss_and_counts(
                p, {"tokens": tokens, "labels": labels}),
            has_aux=True)(params)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    close(grads, want_grads, 1e-4)
    # the correction bias chooses experts and gets no gradient
    for block in grads["blocks"]:
        if "moe" in block:
            assert not np.any(np.asarray(block["moe"]["bias"]))
    assert int(jnp.sum(counts["moe_overflow"])) == 0


def test_weights_have_the_models_layout(reference):
    from repro.models import nemotron_h
    cfg = json.load(open(os.path.join(CONFIG_DIR, "config.json")))
    want, _ = nemotron_h.abstract_init(model_config(cfg),
                                       jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: reference.init_params(cfg, k),
                         jax.ShapeDtypeStruct((2,), jnp.uint32))
    shape = lambda t: jax.tree.map(lambda x: (x.shape, x.dtype), t)
    assert shape(got) == shape(want)
    n = sum(x.size for x in jax.tree.leaves(got))
    assert n == 528_093_120 == model_config(cfg).param_count()


@pytest.mark.parametrize("seed", SEEDS)
def test_grouped_gated_mamba_block_against_the_recurrence(reference, seed):
    """The model's chunked SSD with 2 B/C groups and the gated group-wise
    RMSNorm against the reference's per-position recurrence, one block,
    float32. Tolerance: round-off of the chunked against the sequential
    sums (readings some 1e-6)."""
    from repro.models import mamba2
    cfg = small_config()
    params = T.make_weights(reference, cfg, seed)["blocks"][0]["mamba"]
    u = jax.random.normal(jax.random.PRNGKey(seed % 1000), (2, SEQ, 64))
    d = reference.dims(cfg)
    with jax.default_matmul_precision("highest"):
        want = reference._mamba(d, params, u, None, reference.SCAN_BLOCK)
        got = mamba2.mamba2_apply(model_config(cfg), params, u)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("seed", SEEDS)
def test_expert_shares_add_up_to_the_uncut_layer(reference, seed):
    """Over the 4 shares of 16 experts, each holding 4, the model's held
    experts' parts, plus the shared expert counted once, equal the uncut
    layer of the reference (every expert held). float32; tolerance:
    round-off of the grouped products against the dense ones."""
    from repro.models import moe
    full = small_config(experts_held=16, expert_offset=0)
    p = T.make_weights(reference, full, seed)["blocks"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(seed % 997), (2 * SEQ, 64))
    d = reference.dims(full)
    with jax.default_matmul_precision("highest"):
        want = (reference.held_experts(d, p, x)
                + reference.shared_expert(p["shared"], x))
        got = moe.shared_expert(p["shared"], x)
        rows = 0
        for offset in range(0, 16, 4):
            share = model_config(small_config(experts_held=4,
                                              expert_offset=offset))
            part = dict(p, w_up=p["w_up"][offset:offset + 4],
                        w_down=p["w_down"][offset:offset + 4])
            y, counts = moe.routed_experts(share, part, x)
            got = got + y
            rows += int(jnp.sum(counts["rows"]))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))
    # every token's every choice lands in exactly one share
    assert rows == 2 * SEQ * d.top_k


@pytest.mark.parametrize("bias", ["drawn", "toward_held"])
def test_routing_counts_equal_a_host_count(reference, bias):
    """The layer's counts against a count of the router's choices on the
    host; a bias that sends every token to the held experts fills the
    dropless buffer (T min(k, held) rows) and overflows nothing."""
    from repro.models import moe
    cfg = small_config()
    m = model_config(cfg)
    p = T.make_weights(reference, cfg, 3)["blocks"][1]["moe"]
    if bias == "toward_held":
        p = dict(p, bias=jnp.zeros(16).at[4:8].set(10.0))
    x = jax.random.normal(jax.random.PRNGKey(4), (2 * SEQ, 64))
    with jax.default_matmul_precision("highest"):
        _, ids = moe.route(m, p["router"], p["bias"], x)
        _, counts = moe.routed_experts(m, p, x)
    want = np.bincount(np.asarray(ids).ravel(), minlength=16)[4:8]
    np.testing.assert_array_equal(np.asarray(counts["rows"]), want)
    assert int(counts["overflow"]) == 0
    if bias == "toward_held":
        assert want.sum() == 2 * SEQ * min(m.experts_per_token, 4)


def test_trainer_feeds_the_moe_counters(tmp_path):
    """The ``moe`` counter group holds the sum of every step's routing
    counts, as the step returned them, keyed by MoE layer and expert."""
    from repro import tracing
    from repro.configs.base import TrainConfig
    from repro.launch.train import ADCCTrainer
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
    total = sum(seen)
    assert total.shape == (3, 4) and total.sum() > 0
    for layer in range(3):
        assert group[("overflow", layer)] == 0
        for e in range(4):
            assert group[("rows", layer, 4 + e)] == total[layer, e]


def test_control_rounds_projection_operands(reference):
    cfg = small_config()
    params = T.make_weights(reference, cfg, 5)
    args = batch_of(5, cfg)
    exact, _ = reference.loss_and_grad(cfg, params, *args)
    low, _ = reference.loss_and_grad(cfg, params, *args,
                                     operand_dtype=jnp.float8_e4m3fn)
    assert float(low) != float(exact)
    assert abs(float(low) - float(exact)) < 0.05 * float(exact)


def test_flops_of_the_published_shapes():
    cfg = json.load(open(os.path.join(CONFIG_DIR, "config.json")))
    flops = load_module(os.path.join(CONFIG_DIR, "flops.py"))
    D, S = 2688, 8192
    E, GN, H, P, N, Q = 4096, 8 * 128, 64, 64, 128, 128
    mamba = (D * (2 * E + 2 * GN + H) + E * D + 4 * (E + 2 * GN)
             + Q * GN + Q * H * P + 2 * H * P * N)
    attn = D * 36 * 128 + 4096 * D + 2 * S * 32 * 128
    moe_ = D * 128 + 6 * 8 / 128 * 2 * D * 1856 + 2 * D * 3712
    want = 6.0 * (3 * mamba + attn + 3 * moe_ + D * 16384)
    assert flops.train_flops_per_token(cfg) == pytest.approx(want)
    assert 1.9e9 < want < 2.0e9
    work = flops.attention_kernel_work(cfg)
    product = 2.0 * 2 * 32 * 128 * S * (S + 1) / 2
    assert work["flops"] == pytest.approx((2 * 2 + 3 + 4) * product)
    # compute bound at 8,192 positions on a TPU v5e
    assert work["flops"] / work["bytes"] > 197e12 / 819e9


# ---------------------------------------------------------------------------
# the cell's per-layer readers
# ---------------------------------------------------------------------------

def reader(name):
    return load_module(os.path.join(BENCH_DIR, "metrics", name + ".py")).read


def test_expert_load_ratio_reads_the_moe_counters(monkeypatch):
    from repro import tracing
    group = {("rows", 0, 0): 30, ("rows", 0, 1): 10, ("rows", 1, 0): 20,
             ("rows", 1, 1): 20, ("overflow", 0): 0, ("overflow", 1): 0}
    monkeypatch.setattr(tracing, "counters", lambda: {"moe": dict(group)})
    assert reader("expert_load_ratio")({}) == pytest.approx(30 / 20)
    monkeypatch.setattr(tracing, "counters", lambda: {})
    assert reader("expert_load_ratio")({}) is None
    monkeypatch.setattr(tracing, "counters",
                        lambda: {"moe": {("rows", 0, 0): 0}})
    assert reader("expert_load_ratio")({}) is None
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert reader("expert_load_ratio")({}) is None


def attention_obs(ops, steps=4, name="nemotron3-nano-30b-a3b"):
    cfg = json.load(open(os.path.join(CONFIG_DIR, "config.json")))
    cfg["name"] = name
    return {"config": cfg, "device_kind": "TPU v5 lite",
            "step_walls": {5 + i: 0.4 for i in range(steps)},
            "trace": {"busy_s": 1.0, "window_s": 1.6, "device_ops": ops}}


def test_attention_roofline_reads_the_splash_kernels():
    flops = load_module(os.path.join(CONFIG_DIR, "flops.py"))
    cfg = json.load(open(os.path.join(CONFIG_DIR, "config.json")))
    work = flops.attention_kernel_work(cfg)["flops"]
    # four steps of kernel work at half the bf16 peak (compute bound)
    seconds = 4 * work / (0.5 * 197e12)
    ops = [["fusion.12", 3.0],
           ["splash_mqa_fwd_residuals.2", seconds * 0.3],
           ["splash_mqa_fwd_residuals.3", seconds * 0.2],
           ["splash_mqa_dkv_no_residuals.1", seconds * 0.3],
           ["splash_mqa_dq_no_residuals.1", seconds * 0.2]]
    assert reader("attention_roofline")(attention_obs(ops)) == \
        pytest.approx(50.0)


def test_attention_roofline_reads_nothing_without_its_kernel():
    read = reader("attention_roofline")
    ops = [["fusion.12", 3.0]]
    assert read(attention_obs(ops)) is None
    assert read(dict(attention_obs(ops), trace=None)) is None
    splash = [["splash_mqa_fwd_residuals.2", 0.1]]
    # a configuration without the kernel's work
    assert read(attention_obs(splash, name="mamba2-130m")) is None
    assert read(attention_obs(splash, name="no-such-config")) is None


# ---------------------------------------------------------------------------
# the cell, rehearsed at a tiny cut
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench"))
    TN.make_copy(root)
    return root


def test_tiny_cell_runs_and_is_correct(root):
    from repro import tracing
    tracing.reset()
    ctx = TN.context(root, TN.CELL)
    outcome = ctx.cell.driver().run(ctx)
    assert outcome.correct, outcome.checks
    assert outcome.attempted > 0 and outcome.failed == 0
    e2e = metric_values(ctx.cell, outcome, trace=False)
    assert set(e2e) == {"train_tokens_per_s", "setup_s"}
    layers = metric_values(ctx.cell, outcome, trace=True)
    assert layers["train_mfu"]["value"] > 0
    # no trace on the CPU: the idle share finds nothing
    assert "idle_pct.train" not in layers
    obs = dict(outcome.observations, trace=outcome.trace)
    assert reader("expert_load_ratio")(obs) >= 1.0
    assert reader("attention_roofline")(obs) is None


# a donated step consumes its inputs, so no step can hand them back
# unchanged on top of it: ``unchanged_state`` cannot run in this cell
@pytest.mark.parametrize("fault", sorted(set(FAULTS) - {"unchanged_state"}))
def test_a_broken_step_is_not_correct(root, fault, monkeypatch):
    build = T.build_trainer

    def broken(*args, **kw):
        trainer = build(*args, **kw)
        trainer.step_fn = FAULTS[fault](trainer)
        return trainer

    monkeypatch.setattr(T, "build_trainer", broken)
    ctx = TN.context(root, TN.CELL, seconds=0.2)
    outcome = ctx.cell.driver().run(ctx)
    assert not outcome.correct
    assert [k for k, c in outcome.checks.items() if not c.ok], outcome.checks

