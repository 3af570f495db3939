"""Pipeline parallelism: GPipe schedule via shard_map + ppermute.

Stages live on a dedicated mesh axis; each device holds one stage's
parameters (leading ``stages`` dim, sharded on the axis). The schedule
runs ``n_micro + n_stages - 1`` ticks of a ``lax.scan``; per tick every
device applies its stage to its current activation and passes the result
to the next stage with a single ``collective-permute`` (ring neighbor
exchange — the cheapest collective in the roofline's collective term).
Stage 0 ingests microbatch ``t``; the last stage emits microbatch
``t - (n_stages - 1)``. Bubble fraction = (n_stages-1)/(n_micro+n_stages-1),
the standard GPipe overhead — amortized by more microbatches.

Composes with the rest of the stack: inside each stage the layer fn can
still use TP/FSDP sharding on the remaining mesh axes.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply", "stage_params"]


def stage_params(params_stacked, n_stages: int):
    """Reshape (L, ...) stacked layer params into (n_stages, L/n_stages, ...)
    per-stage groups."""
    def split(x):
        L = x.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])

    return jax.tree.map(split, params_stacked)


def pipeline_apply(stage_fn: Callable, params_staged, x_micro: jax.Array,
                   mesh, *, axis: str = "stage"):
    """Run the GPipe schedule.

    stage_fn(stage_local_params, act) -> act
        applies ONE stage's layer group; sees params with the leading
        per-stage layer dim (L/n_stages, ...).
    params_staged: leaves (n_stages, L/n_stages, ...), sharded over
        ``axis`` on dim 0.
    x_micro: (n_micro, mb, ...) microbatched input activations
        (replicated over ``axis``).
    Returns (n_micro, mb, ...) outputs of the final stage.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    def shard_body(params_local, xs_local):
        # params_local: (1, L/n_stages, ...) — this device's stage
        p_stage = jax.tree.map(lambda w: w[0], params_local)
        stage_idx = jax.lax.axis_index(axis)
        act_shape = xs_local.shape[1:]
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            act, outs = carry
            # stage 0 ingests microbatch t (clamped; masked later)
            mb = jax.lax.dynamic_index_in_dim(
                xs_local, jnp.minimum(t, n_micro - 1), 0, keepdims=False)
            act_in = jnp.where(stage_idx == 0, mb, act)
            act_out = stage_fn(p_stage, act_in)
            # last stage emits microbatch t - (n_stages - 1)
            out_t = t - (n_stages - 1)
            emit = (stage_idx == n_stages - 1) & (out_t >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, act_out.astype(o.dtype), jnp.maximum(out_t, 0), 0),
                lambda o: o, outs)
            # ring-shift activations to the next stage
            act_next = jax.lax.ppermute(act_out, axis, perm)
            return (act_next, outs), None

        act0 = jnp.zeros(act_shape, x_micro.dtype)
        outs0 = jnp.zeros((n_micro,) + act_shape, x_micro.dtype)
        (act, outs), _ = jax.lax.scan(tick, (act0, outs0),
                                      jnp.arange(ticks))
        # every device returns a buffer; only the last stage's is real.
        # psum over a one-hot mask broadcasts it to all (cheap: outputs
        # are per-microbatch activations, one all-reduce at the end).
        mask = (stage_idx == n_stages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, axis)

    in_specs = (
        jax.tree.map(lambda _: P(axis), params_staged),
        P(),
    )
    return jax.shard_map(shard_body, mesh=mesh, in_specs=in_specs,
                         out_specs=P(), check_vma=False)(params_staged,
                                                         x_micro)
