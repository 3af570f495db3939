"""Jittable train_step / serve_step builders with full sharding specs.

This is the single construction site used by the dry-run (lower+compile
against ShapeDtypeStructs), the real trainer (launch/train.py), and the
benchmarks — so what we roofline is exactly what we'd run.

train_step(params, opt_state, err_state, batch, rng) ->
    (new_params, new_opt_state, err_state, metrics, update_checksums)

``metrics`` holds the loss, the gradient norm and, for a family whose
``ModelApi.loss_and_counts`` reports them, the routing counts
(``moe_rows``, ``moe_overflow``). ``donate=True`` donates params, optimizer
and error-feedback state to the outputs (``TrainConfig.donate_state`` in
the trainer).

The ``update_checksums`` output is the ADCC hook (paper §III.C adapted —
DESIGN.md §2): one f32 scalar per parameter tensor, the sum of the step's
applied update. Because optimizer updates are applied *additively*, the
persistent per-tensor checksum evolves as ``checksum += sum(update)`` — a
tiny synchronous write per step (the "flush one cache line" analogue)
that lets recovery verify which asynchronously-written state slots are
consistent (core/acc_state.py). Computing these sums costs one fused
reduction per tensor inside the already-jitted step: ignorable, exactly
as the paper requires.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs.base import TrainConfig
from ..models.registry import ModelApi
from ..optim import compress_decompress, make_optimizer
from ..optim.adamw import AdafactorState, AdamWState
from ..sharding.partition import (ROUTER_F32, PartitionRules,
                                  cache_shardings, params_shardings)

__all__ = ["build_train_step", "build_serve_step", "tree_checksums",
           "build_opt_shardings"]


def tree_checksums(tree) -> Any:
    """Per-leaf scalar checksums (f32 sums). Linear in the leaf, hence
    incrementally maintainable across additive updates."""
    return jax.tree.map(lambda x: jnp.sum(x.astype(jnp.float32)), tree)


def build_opt_shardings(tcfg: TrainConfig, rules: PartitionRules,
                        params_sh, axes):
    """Optimizer-state shardings. AdamW moments mirror their parameter's
    sharding exactly; Adafactor's factored stats drop the reduced logical
    dim (row stats lose the last axis, col stats the second-to-last)."""
    mesh = rules.mesh
    repl = NamedSharding(mesh, P())
    if tcfg.optimizer == "adafactor":
        is_axes = lambda t: (isinstance(t, tuple)
                             and all(isinstance(s, str) for s in t))

        def stat_sharding(ax):
            if len(ax) >= 2:
                return {
                    "row": NamedSharding(mesh, rules.spec(ax[:-1])),
                    "col": NamedSharding(mesh, rules.spec(ax[:-2] + ax[-1:])),
                }
            return {"v": NamedSharding(mesh, rules.spec(ax))}

        stats = jax.tree.map(stat_sharding, axes, is_leaf=is_axes)
        return AdafactorState(step=repl, stats=stats)
    return AdamWState(step=repl, m=params_sh, v=params_sh)


def build_train_step(api: ModelApi, tcfg: TrainConfig,
                     rules: PartitionRules, *, donate: bool = True,
                     batch_template=None):
    """Returns (jitted train_step, shardings dict, opt_init).

    ``batch_template``: pytree of arrays/ShapeDtypeStructs matching the
    batch — used to pin explicit DP input shardings (leaving the batch
    unannotated lets GSPMD replicate activations across the data axis)."""
    mesh = rules.mesh
    opt_init, opt_update = make_optimizer(tcfg)
    use_compression = tcfg.grad_compression == "int8"

    compute_dtype = jnp.dtype(api.cfg.compute_dtype)

    params_shapes, axes = api.abstract_init(jax.random.PRNGKey(0))

    def to_compute(w, ax):
        # bf16 compute copy of >=2D weights, cast *before* the layer scan
        # so FSDP all-gathers move bf16, not f32 masters (§Perf iter 3);
        # 1D params (norms, A_log, dt_bias) stay f32 for numerics, and so
        # does a weight its layer marks with the ROUTER_F32 axis
        if w.dtype == jnp.float32 and w.ndim >= 2 and ROUTER_F32 not in ax:
            return w.astype(compute_dtype)
        return w

    # a family with routing counts reports them beside the loss
    with_counts = api.loss_and_counts is not None

    def train_step(params, opt_state, err_state, batch, rng):
        def loss_of(p):
            fn = api.loss_and_counts if with_counts else api.loss_fn
            return fn(jax.tree.map(to_compute, p, axes), batch, mesh,
                      remat=tcfg.remat)

        # stable names for the device's operations, whatever the HLO
        # numbering: model, optimizer, adcc.checksums
        with jax.named_scope("model"):
            if with_counts:
                (loss, counts), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(params)
            else:
                loss, grads = jax.value_and_grad(loss_of)(params)
                counts = {}
        with jax.named_scope("optimizer"):
            if use_compression:
                grads, err_state = compress_decompress(grads, err_state, rng)
            updates, new_opt_state = opt_update(grads, opt_state, params)
            new_params = jax.tree.map(
                lambda p, u: p + u.astype(p.dtype), params, updates)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        metrics = {"loss": loss.astype(jnp.float32), "grad_norm": gnorm,
                   **counts}
        # ADCC scalars: direct sums of the new state fuse into the update's
        # HBM pass (the tensors are already streaming through registers);
        # the update sums additionally give the *linearity chain*
        # cks_params[t] == cks_params[t-1] + cks_updates[t] used to verify
        # the ledger itself (core/acc_state.py).
        with jax.named_scope("adcc.checksums"):
            checksums = {
                "params": tree_checksums(new_params),
                "opt": tree_checksums(new_opt_state),
                "updates": tree_checksums(updates),
            }
        return new_params, new_opt_state, err_state, metrics, checksums

    # --- shardings -----------------------------------------------------------
    params_sh = params_shardings(rules, axes)
    opt_sh = build_opt_shardings(tcfg, rules, params_sh, axes)
    err_sh = params_sh  # error-feedback buffers mirror params
    repl = NamedSharding(mesh, P())
    metrics_sh = repl     # every metric, routing counts included
    checksums_sh = {
        "params": jax.tree.map(lambda _: repl, params_sh),
        "opt": jax.tree.map(lambda _: repl, opt_sh),
        "updates": jax.tree.map(lambda _: repl, params_sh),
    }
    from ..sharding.partition import batch_shardings
    batch_sh = (batch_shardings(rules, batch_template)
                if batch_template is not None else None)

    jitted = jax.jit(
        train_step,
        in_shardings=(params_sh, opt_sh, err_sh, batch_sh, repl),
        out_shardings=(params_sh, opt_sh, err_sh, metrics_sh, checksums_sh),
        donate_argnums=(0, 1, 2) if donate else (),
    )
    shardings = {"params": params_sh, "opt": opt_sh, "err": err_sh,
                 "axes": axes, "params_shapes": params_shapes}
    return jitted, shardings, opt_init


def build_serve_step(api: ModelApi, rules: PartitionRules, *,
                     batch: int, max_len: int, donate: bool = True):
    """One-token decode step builder. Returns (jitted serve_step,
    shardings dict)."""
    cfg = api.cfg
    mesh = rules.mesh
    assert api.decode_step is not None, f"{cfg.name} has no decode step"

    def serve_step(params, cache, tokens, pos):
        logits, new_cache = api.decode_step(params, cache, tokens, pos, mesh)
        return logits, new_cache

    params_shapes, axes = api.abstract_init(jax.random.PRNGKey(0))
    params_sh = params_shardings(rules, axes)

    box = {}

    def cache_only():
        c, a = api.init_cache(batch, max_len)
        box["axes"] = a
        return c

    cache_shapes = jax.eval_shape(cache_only)
    cache_sh = cache_shardings(rules, box["axes"])
    dp = rules.table["batch"]
    repl = NamedSharding(mesh, P())
    tok_sh = NamedSharding(mesh, P(dp, None)) if dp is not None else repl
    # decode_step slices logits back to the *true* vocab (tables are
    # padded); keep the vocab dim sharded only when it still divides TP
    vocab_ax = "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None
    logits_sh = NamedSharding(mesh, P(dp, None, vocab_ax))

    jitted = jax.jit(
        serve_step,
        in_shardings=(params_sh, cache_sh, tok_sh, repl),
        out_shardings=(logits_sh, cache_sh),
        donate_argnums=(1,) if donate else (),
    )
    shardings = {"params": params_sh, "cache": cache_sh,
                 "params_shapes": params_shapes,
                 "cache_shapes": cache_shapes, "axes": axes,
                 "cache_axes": box["axes"]}
    return jitted, shardings
