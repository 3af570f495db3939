"""The nemotron_h family through the trainer: a donated step against an
undonated one (bit-identical losses and ledger records, and a crash
recovered from its slots), the mamba2-130m step's program unchanged
by what the family added to the shared code, and the held experts'
ladder of buffer sizes."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig, TrainConfig
from repro.core.acc_state import ChecksumLedger
from repro.launch.mesh import single_device_mesh
from repro.launch.steps import build_train_step
from repro.launch.train import ADCCTrainer
from repro.models import moe
from repro.models.registry import build_model, get_config
from repro.optim import init_error_state
from repro.sharding.partition import make_rules

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# sha256 of the lowered step (StableHLO text) of the benchmark's
# mamba2-130m configuration (batch 8 x seq 2048), as the tree before the
# nemotron_h family lowered it: the grouped B/C, the gated norm, the
# routing counts, the float32 router and the donation switch leave it
# unchanged, op for op
MAMBA2_130M_STEP = ("f7ed4cd0ec6d03ba20801376c6fbf35c"
                    "36310737beec618befcfe1740c79a85d")


# the same for the reduced steps (batch 2 x seq 64) of the two families
# whose softmax router is also named ``router``: the held-experts layer's
# float32 router is marked by its own axis, so their compute copy still
# casts their router to bfloat16
MOE_STEPS = {
    "deepseek-v2-lite-16b": ("61d7c55cdb56f9fa888de581c8150875"
                             "6534dda11516fcf45a5cec2e226ca5d3"),
    "kimi-k2-1t-a32b": ("b23fe96ad9c6f74655e0ca4d5f3892ac"
                        "8824ace9553d190b91c91159ae9303ca"),
}


def small(**kw):
    cfg = get_config("nemotron3-nano-30b-a3b").reduced()
    return dataclasses.replace(cfg, experts_held=4, expert_offset=4, **kw)


def trainer(workdir, donate, slot_every=4):
    tcfg = TrainConfig(remat="dots", total_steps=40, warmup_steps=5,
                       donate_state=donate)
    return ADCCTrainer(small(), tcfg, workdir, batch=2, seq=32,
                       slot_every=slot_every)


def test_reduced_config_keeps_the_family_switches():
    cfg = small()
    assert cfg.layer_pattern == "M*E" and cfg.n_layers == 3
    assert cfg.ssm_groups == 2 and cfg.ssm_gated_norm
    assert cfg.routed_scaling == 2.5 and cfg.n_held_experts == 4
    assert not cfg.use_rope and cfg.shared_d_ff


def test_donated_step_matches_the_undonated_one(tmp_path):
    """Donation changes where the state lives, not what the step computes:
    the losses and every ledger record agree bit for bit."""
    runs = {}
    for donate in (False, True):
        d = str(tmp_path / str(donate))
        res = trainer(d, donate).run(6, log_every=0)
        recs = ChecksumLedger(os.path.join(d, "ledger.jsonl")).read_all()
        runs[donate] = (res.losses, [r.to_json() for r in recs])
    assert runs[True] == runs[False]
    assert len(runs[True][1]) == 6


def test_donated_run_recovers_bitwise_after_a_crash(tmp_path):
    ref = trainer(str(tmp_path / "ref"), True)
    r_ref = ref.run(12, log_every=0)
    crash_dir = str(tmp_path / "crash")
    trainer(crash_dir, True).run(12, crash_at_step=9, log_every=0)
    again = trainer(crash_dir, True)
    r2 = again.run(12, log_every=0)
    assert r2.resumed_from is not None and r2.resumed_from >= 3
    start = r2.resumed_from + 1
    assert r2.losses == r_ref.losses[start:]
    diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         ref._final_params, again._final_params)
    assert max(jax.tree.leaves(diffs)) == 0.0


def step_program_sha256(model: ModelConfig, train: TrainConfig,
                        batch: int, seq: int) -> str:
    """sha256 of the lowered train step (StableHLO text), undonated, on
    one device."""
    api = build_model(model)
    rules = make_rules(single_device_mesh(), fsdp=train.fsdp)
    shapes = {k: jax.ShapeDtypeStruct((batch, seq), jnp.int32)
              for k in ("tokens", "labels")}
    step, _, opt_init = build_train_step(api, train, rules, donate=False,
                                         batch_template=shapes)
    params, _ = api.abstract_init(jax.random.PRNGKey(0))
    text = step.lower(params, jax.eval_shape(opt_init, params),
                      jax.eval_shape(init_error_state, params), shapes,
                      jax.eval_shape(lambda: jax.random.PRNGKey(0))
                      ).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


def test_mamba2_130m_step_program_is_unchanged():
    with open(os.path.join(ROOT, "bench", "configs", "mamba2-130m",
                           "config.json")) as fh:
        cfg = json.load(fh)
    got = step_program_sha256(ModelConfig(**cfg["model"]),
                              TrainConfig(**cfg["train"]),
                              cfg["shape"]["batch"], cfg["shape"]["seq"])
    assert got == MAMBA2_130M_STEP


@pytest.mark.parametrize("arch", sorted(MOE_STEPS))
def test_moe_family_step_program_is_unchanged(arch):
    got = step_program_sha256(get_config(arch).reduced(), TrainConfig(),
                              batch=2, seq=64)
    assert got == MOE_STEPS[arch]


@pytest.mark.parametrize("donate", [False, True])
def test_step_reports_routing_counts(donate):
    cfg = small()
    api = build_model(cfg)
    tcfg = TrainConfig(remat="none", donate_state=donate)
    rules = make_rules(single_device_mesh(), fsdp=True)
    batch = {k: jnp.zeros((2, 32), jnp.int32) for k in ("tokens", "labels")}
    step, _, opt_init = build_train_step(api, tcfg, rules, donate=donate,
                                         batch_template=batch)
    params, _ = api.init(jax.random.PRNGKey(0))
    out = step(params, opt_init(params), init_error_state(params), batch,
               jax.random.PRNGKey(1))
    metrics = out[3]
    assert metrics["moe_rows"].shape == (1, 4)
    assert int(metrics["moe_overflow"][0]) == 0
    # every token picks 2 of 8 experts; the held 4 take some of them
    assert 0 < int(jnp.sum(metrics["moe_rows"])) <= 2 * 32 * 2


# ---------------------------------------------------------------------------
# the held experts' ladder of buffer sizes
# ---------------------------------------------------------------------------

# (experts per token, held experts, the router's experts): the benchmark
# cell's layer, the reduced configurations', and shares that hold one,
# a few or every expert
SHARES = [(6, 8, 128), (2, 4, 8), (3, 4, 16), (1, 1, 16), (6, 16, 16),
          (8, 2, 256), (2, 64, 64)]


@pytest.mark.parametrize("tokens", [1, 2, 64, 1000, 8192, 16384])
def test_ladder_top_rung_is_the_dropless_buffer(tokens):
    """The largest buffer takes every routing; each next is half the one
    above rounded up to 128 rows, strictly smaller, and the ladder stops
    at the first at or below twice the balanced held load."""
    for K, held, E in SHARES:
        ladder = moe.buffer_ladder(tokens, K, held, E)
        assert ladder[0] == tokens * min(K, held)
        floor = 2 * tokens * K * held / E
        for above, r in zip(ladder, ladder[1:]):
            assert r % 128 == 0 and r < above
            assert r == -(-(-(-above // 2)) // 128) * 128
            assert above > floor
        # it stops at the floor, or where halving no longer shrinks it
        last = ladder[-1]
        assert last <= floor or -(-(-(-last // 2)) // 128) * 128 >= last


def test_the_cells_ladder():
    """nemotron3-nano-30b-a3b's MoE layer: 16,384 tokens, top-6 of 128,
    8 held; the floor is twice its balanced load of 6,144 rows."""
    assert moe.buffer_ladder(16384, 6, 8, 128) == (98304, 49152, 24576,
                                                   12288)


@pytest.mark.parametrize("tokens", [64, 1000, 16384])
def test_the_rung_takes_the_held_rows_and_less_than_twice(tokens):
    """The rung chosen is the smallest that takes the held rows; above
    the ladder's floor it is under twice them; the device's choice is the
    host's."""
    index = jax.jit(moe.rung_index, static_argnums=0)
    for K, held, E in SHARES:
        ladder = moe.buffer_ladder(tokens, K, held, E)
        held_rows = {0, 1, ladder[-1], ladder[-1] + 1, ladder[0],
                     *range(0, ladder[0], max(1, ladder[0] // 37))}
        for n in sorted(n for n in held_rows if n <= ladder[0]):
            r = moe.buffer_rung(ladder, n)
            assert r >= n and r == min(x for x in ladder if x >= n)
            if r != ladder[-1]:
                assert r < 2 * n
            assert ladder[int(index(ladder, n))] == r


@pytest.mark.parametrize("batch", [1, 2, 8, 16])
def test_decode_sizes_are_one_rung(batch):
    """One token per row: the cell's layer at decode batch sizes, and any
    buffer of at most 128 rows, is the one buffer it had."""
    assert moe.buffer_ladder(batch, 6, 8, 128) == (batch * 6,)
    for K, held, E in SHARES:
        ladder = moe.buffer_ladder(batch, K, held, E)
        if ladder[0] <= 128:
            assert ladder == (batch * min(K, held),)
