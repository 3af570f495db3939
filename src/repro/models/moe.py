"""Mixture-of-Experts FFN with expert parallelism.

Three execution paths:

* ``moe_apply_dense`` — reference path: every expert computed on every
  token with mask-combine. O(T·E·F) compute, zero collectives. Used as
  the smoke-test/correctness oracle and for tiny reduced configs.

* ``moe_apply_ep`` — expert-parallel path under ``jax.shard_map``: tokens
  sharded over every mesh axis, experts sharded over the EP axis
  ("model"). Per shard: top-k routing -> capacity-bucketed all_to_all to
  expert owners -> local grouped GEMM (sorted by local expert) ->
  all_to_all back -> weighted combine at the source. This is the
  TPU-native (GSPMD/ICI) analogue of the dispatch pipelines GPU MoE
  stacks build with NCCL all-to-alls; the collective bytes it emits are
  exactly what the roofline's collective term measures.

  Capacity: each destination device receives at most
  ``ceil(T_loc * K * capacity_factor / ep)`` tokens; overflow
  assignments are dropped (weights renormalized upstream make this a
  standard capacity-drop MoE). Tests run with generous capacity and
  assert the EP path matches the dense oracle exactly.

* ``moe_held_apply`` — one chip's share of an expert-parallel layer
  (the ``nemotron_h`` family): the layer is told which experts it holds
  (``cfg.expert_offset``, ``cfg.experts_held`` of ``cfg.n_experts``),
  routes every token over all of them and computes only its held
  experts' part of the result, plus the shared expert. Dropless: the
  assignments to held experts are gathered in expert order into a buffer
  of static size ``T * min(K, held)``, which no routing can overflow, and
  run as grouped products (``jax.lax.ragged_dot``) over the routed
  counts. Its router is DeepSeek-V3's: float32 logits, sigmoid scores,
  the top-k of the scores plus a correction bias (used for the choice
  alone), the chosen scores renormalized to sum 1 and scaled by
  ``cfg.routed_scaling``. Its experts, routed and shared, are relu^2
  MLPs with no gate: ``W_down relu(W_up x)^2``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..sharding.partition import ROUTER_F32
from .layers import Axes, Params, dense_init

__all__ = ["moe_init", "moe_apply_dense", "moe_apply_ep", "router_topk",
           "moe_held_init", "moe_held_apply", "route", "routed_experts",
           "shared_expert"]

HIGHEST = jax.lax.Precision.HIGHEST


def moe_init(cfg: ModelConfig, key) -> Tuple[Params, Axes]:
    D, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p, a = {}, {}
    p["router"], a["router"] = dense_init(ks[0], D, E, "embed", "experts_r",
                                          jnp.float32)

    def expert_stack(k, din, dout):
        sub = jax.random.split(k, E)
        w = jax.vmap(lambda kk: jax.random.normal(kk, (din, dout), jnp.float32)
                     * (2.0 / (din + dout)) ** 0.5)(sub)
        return w.astype(dtype)

    p["w_gate"] = expert_stack(ks[1], D, F)
    a["w_gate"] = ("experts", "embed", "mlp_e")
    p["w_up"] = expert_stack(ks[2], D, F)
    a["w_up"] = ("experts", "embed", "mlp_e")
    p["w_down"] = expert_stack(ks[3], F, D)
    a["w_down"] = ("experts", "mlp_e", "embed")
    return p, a


def router_topk(cfg: ModelConfig, router_w: jax.Array, x: jax.Array):
    """(weights (T,K) f32 renormalized, ids (T,K) int32) for tokens (T,D)."""
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.experts_per_token)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, ids.astype(jnp.int32)


# ---------------------------------------------------------------------------
# dense oracle
# ---------------------------------------------------------------------------

def moe_apply_dense(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    """x: (T, D) -> (T, D). Computes every expert on every token."""
    dt = x.dtype
    weights, ids = router_topk(cfg, p["router"], x)      # (T,K)
    E = cfg.n_experts
    # (T, E) combine weights
    combine = jnp.zeros((x.shape[0], E), jnp.float32)
    combine = combine.at[jnp.arange(x.shape[0])[:, None], ids].add(weights)
    gate = jax.nn.silu(jnp.einsum("td,edf->tef", x, p["w_gate"].astype(dt)))
    up = jnp.einsum("td,edf->tef", x, p["w_up"].astype(dt))
    y = jnp.einsum("tef,efd->ted", gate * up, p["w_down"].astype(dt))
    return jnp.einsum("ted,te->td", y.astype(jnp.float32),
                      combine).astype(dt)


# ---------------------------------------------------------------------------
# expert-parallel production path
# ---------------------------------------------------------------------------

def _local_expert_ffn_ragged(x_sorted: jax.Array, group_sizes: jax.Array,
                             wg: jax.Array, wu: jax.Array, wd: jax.Array):
    """Grouped SwiGLU via jax.lax.ragged_dot. NOTE: the reference (CPU)
    lowering of ragged_dot is dense-per-group — E_loc x the useful flops
    (measured 24x on kimi-k2; §Perf iteration 7). Kept as an option for
    backends with native ragged support."""
    dt = x_sorted.dtype
    gate = jax.nn.silu(jax.lax.ragged_dot(x_sorted, wg.astype(dt), group_sizes))
    up = jax.lax.ragged_dot(x_sorted, wu.astype(dt), group_sizes)
    return jax.lax.ragged_dot(gate * up, wd.astype(dt), group_sizes)


def _local_expert_ffn(x_sorted: jax.Array, group_sizes: jax.Array,
                      wg: jax.Array, wu: jax.Array, wd: jax.Array,
                      block_factor: float = 2.0):
    """Equal-capacity grouped SwiGLU: scan over local experts, each
    processing a static ``cap``-row window of the expert-sorted rows
    (dynamic_slice at its group offset). Static shapes, MXU-aligned, and
    total flops = E_loc x cap x ffn ≈ block_factor x useful — vs the
    E_loc x dense cost of the reference ragged_dot lowering (§Perf
    iteration 7: 12x compute-term win on kimi-k2 train).

    Rows beyond ``cap`` within one expert's group are dropped (standard
    capacity semantics; combine weights upstream make this a no-op for
    the kept rows). Overlapping windows self-heal: expert e's masked
    zero tail is overwritten by expert e+1's correct rows.
    """
    R, D = x_sorted.shape
    E_loc = wg.shape[0]
    dt = x_sorted.dtype
    cap = int(-(-R * block_factor // E_loc))
    cap = max(8, ((cap + 7) // 8) * 8)           # sublane-aligned
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(group_sizes)[:-1].astype(jnp.int32)])
    # pad so dynamic_slice never clamps (clamping would misalign writes)
    x_pad = jnp.pad(x_sorted, ((0, cap), (0, 0)))
    y_pad = jnp.zeros((R + cap, D), dt)

    def body(y, inp):
        off, gs, wg_e, wu_e, wd_e = inp
        blk = jax.lax.dynamic_slice(x_pad, (off, 0), (cap, D))
        keep = (jnp.arange(cap) < gs)[:, None]
        h = jax.nn.silu(blk @ wg_e.astype(dt)) * (blk @ wu_e.astype(dt))
        out = jnp.where(keep, h @ wd_e.astype(dt), 0.0).astype(dt)
        return jax.lax.dynamic_update_slice(y, out, (off, 0)), None

    y_pad, _ = jax.lax.scan(
        body, y_pad,
        (offsets, group_sizes.astype(jnp.int32), wg, wu, wd))
    return y_pad[:R]


def _ep_shard_fn(cfg: ModelConfig, ep_axis: str, ep: int, capacity: int):
    """Builds the per-shard function executed under shard_map."""
    K = cfg.experts_per_token
    E = cfg.n_experts
    E_loc = E // ep

    def fn(x, router_w, wg, wu, wd):
        # x: (T, D) local tokens; wg/wu/wd: (E_loc, ., .) local experts
        T, D = x.shape
        weights, ids = router_topk(cfg, router_w, x)     # (T, K)
        fids = ids.reshape(-1)                           # (T*K,)
        fw = weights.reshape(-1)
        dest = fids // E_loc                             # owning device
        lid = fids % E_loc                               # local expert id

        # rank of each assignment within its destination bucket
        onehot = (dest[:, None] == jnp.arange(ep)[None, :]).astype(jnp.int32)
        rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                                   dest[:, None], axis=1)[:, 0]
        keep = rank < capacity                           # capacity drop
        slot = dest * capacity + jnp.where(keep, rank, 0)

        # scatter token payloads + local-expert ids into send buffers
        tok = jnp.repeat(x, K, axis=0)                   # (T*K, D)
        send = jnp.zeros((ep * capacity, D), x.dtype)
        send = send.at[slot].set(jnp.where(keep[:, None], tok, 0.0),
                                 mode="drop")
        # empty/dropped slots carry lid = E_loc: a "trash group" that
        # sorts after every real expert and is never computed
        send_lid = jnp.full((ep * capacity,), E_loc, jnp.int32)
        send_lid = send_lid.at[slot].set(jnp.where(keep, lid, E_loc),
                                         mode="drop")

        # exchange with expert owners
        recv = jax.lax.all_to_all(send.reshape(ep, capacity, D), ep_axis,
                                  split_axis=0, concat_axis=0)
        recv_lid = jax.lax.all_to_all(send_lid.reshape(ep, capacity), ep_axis,
                                      split_axis=0, concat_axis=0)
        rx = recv.reshape(ep * capacity, D)
        rlid = recv_lid.reshape(ep * capacity)

        # grouped GEMM over local experts (sort by local expert id)
        order = jnp.argsort(rlid)
        inv = jnp.argsort(order)
        gs = jnp.bincount(rlid, length=E_loc).astype(jnp.int32)
        y_sorted = _local_expert_ffn(rx[order], gs, wg, wu, wd)
        y = y_sorted[inv]

        # return trip + combine at source
        back = jax.lax.all_to_all(y.reshape(ep, capacity, D), ep_axis,
                                  split_axis=0, concat_axis=0)
        flat = back.reshape(ep * capacity, D)
        y_assign = flat[slot] * (keep & True)[:, None].astype(flat.dtype)
        y_tok = (y_assign.astype(jnp.float32).reshape(T, K, D)
                 * fw.reshape(T, K, 1)).sum(axis=1)
        return y_tok.astype(x.dtype)

    return fn


def moe_apply_ep(cfg: ModelConfig, p: Params, x_tokens: jax.Array,
                 mesh: jax.sharding.Mesh, *,
                 token_axes: Tuple[str, ...], ep_axis: str = "model",
                 capacity: Optional[int] = None) -> jax.Array:
    """x_tokens: (N, D) global token view; N divisible by mesh.size.
    Experts sharded over ``ep_axis``; tokens over ``token_axes``."""
    ep = mesh.shape[ep_axis]
    assert cfg.n_experts % ep == 0, (cfg.n_experts, ep)
    n_total = 1
    for a in token_axes:
        n_total *= mesh.shape[a]
    n_tokens = x_tokens.shape[0]
    pad = (-n_tokens) % n_total  # decode batches can be < mesh size
    if pad:
        x_tokens = jnp.pad(x_tokens, ((0, pad), (0, 0)))
    T_loc = x_tokens.shape[0] // n_total
    if capacity is None:
        capacity = max(1, int(-(-T_loc * cfg.experts_per_token
                                * cfg.capacity_factor // ep)))

    fn = _ep_shard_fn(cfg, ep_axis, ep, capacity)
    tok_spec = P(token_axes, None)
    out = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(tok_spec, P(), P(ep_axis, None, None),
                  P(ep_axis, None, None), P(ep_axis, None, None)),
        out_specs=tok_spec,
        check_vma=False,
    )(x_tokens, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out[:n_tokens] if pad else out


# ---------------------------------------------------------------------------
# one chip's share of an expert-parallel layer (nemotron_h)
# ---------------------------------------------------------------------------

def moe_held_init(cfg: ModelConfig, key) -> Tuple[Params, Axes]:
    """Router over all ``n_experts`` (float32, with its correction bias),
    the held experts' relu^2 MLPs and the shared expert. The router's
    output axis is ``ROUTER_F32``, which keeps it float32 in the train
    step's compute copy."""
    D, F = cfg.d_model, cfg.moe_d_ff
    E_h, Fs = cfg.n_held_experts, cfg.shared_d_ff
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p, a = {}, {}
    p["router"], a["router"] = dense_init(ks[0], D, cfg.n_experts, "embed",
                                          ROUTER_F32, jnp.float32)
    p["bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    a["bias"] = (ROUTER_F32,)

    def expert_stack(k, din, dout):
        w = jax.random.normal(k, (E_h, din, dout), jnp.float32)
        return (w * (2.0 / (din + dout)) ** 0.5).astype(dtype)

    p["w_up"] = expert_stack(ks[1], D, F)
    a["w_up"] = ("experts", "embed", "mlp_e")
    p["w_down"] = expert_stack(ks[2], F, D)
    a["w_down"] = ("experts", "mlp_e", "embed")
    shared, sa = {}, {}
    shared["w_up"], sa["w_up"] = dense_init(ks[3], D, Fs, "embed", "mlp",
                                            dtype)
    shared["w_down"], sa["w_down"] = dense_init(ks[4], Fs, D, "mlp",
                                                "embed", dtype)
    p["shared"], a["shared"] = shared, sa
    return p, a


def route(cfg: ModelConfig, router_w: jax.Array, bias: jax.Array,
          x: jax.Array):
    """(weights (T,K) f32, ids (T,K) int32) over all ``n_experts`` for
    tokens x (T,D): sigmoid scores of the float32 logits, the top-k of
    scores + bias, the chosen scores renormalized and scaled."""
    logits = jnp.matmul(x.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + bias, cfg.experts_per_token)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * cfg.routed_scaling, ids.astype(jnp.int32)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def routed_experts(cfg: ModelConfig, p: Params, x: jax.Array):
    """The held experts' part of the layer for tokens x (T,D), and the
    routing's counts: ``rows`` (held,) routed to each held expert and
    ``overflow``, the held assignments the buffer could not take (0 by
    construction)."""
    T, D = x.shape
    K, E_h = cfg.experts_per_token, cfg.n_held_experts
    dt = x.dtype
    with jax.named_scope("moe.router"):
        weights, ids = route(cfg, p["router"], p["bias"], x)
        local = ids.reshape(-1) - cfg.expert_offset
        # assignments to absent experts sort after every held one
        key = jnp.where((local >= 0) & (local < E_h), local, E_h)
        rows = jnp.bincount(key, length=E_h + 1).astype(jnp.int32)
        order = jnp.argsort(key, stable=True)[:T * min(K, E_h)]
        held = key[order] < E_h
        token = order // K
        w = jnp.where(held, weights.reshape(-1)[order], 0.0)
        overflow = jnp.sum(rows[:E_h]) - jnp.sum(held, dtype=jnp.int32)
    with jax.named_scope("moe.experts"):
        # the TPU's grouped product leaves the rows past the groups
        # undefined: every product's output is selected to its held rows
        # (a zero weight would keep a NaN), so what each product reads,
        # forward and backward, is finite
        def grouped(a, w_e):
            return jnp.where(held[:, None], jax.lax.ragged_dot(
                a, w_e.astype(dt), rows[:E_h]), 0)

        xs = jnp.where(held[:, None], x[token], 0).astype(dt)
        y = grouped(_relu2(grouped(xs, p["w_up"])), p["w_down"])
        out = jnp.zeros((T, D), jnp.float32).at[token].add(
            y.astype(jnp.float32) * w[:, None])
    return out.astype(dt), {"rows": rows[:E_h], "overflow": overflow}


def shared_expert(p: Params, x: jax.Array) -> jax.Array:
    dt = x.dtype
    with jax.named_scope("moe.shared"):
        return _relu2(x @ p["w_up"].astype(dt)) @ p["w_down"].astype(dt)


def moe_held_apply(cfg: ModelConfig, p: Params, x: jax.Array):
    """x (T,D) -> (held experts' part + shared expert (T,D), counts)."""
    y, counts = routed_experts(cfg, p, x)
    return y + shared_expert(p["shared"], x), counts
