"""Shared transformer layers: norms, RoPE/M-RoPE, GQA attention (XLA,
Pallas flash for prefill, splash for a family that asks to train on it on
one TPU chip), SwiGLU.

Conventions
-----------
* Pure-functional: params are nested dicts of jnp arrays; every ``init_*``
  returns ``(params, axes)`` where ``axes`` mirrors params with tuples of
  *logical dimension names* consumed by sharding/partition.py:

    embed    model width D            -> FSDP axis ("data") when enabled
    qheads   fused H*head_dim         -> TP axis ("model")
    kvheads  fused KV*head_dim        -> replicated (KV < TP in all archs)
    mlp      FFN hidden F             -> TP axis ("model")
    vocab    vocabulary               -> TP axis ("model")
    experts  MoE expert count         -> EP axis ("model")
    layers   stacked-scan leading dim -> never sharded

* Compute runs in ``cfg.compute_dtype`` (bf16 by default); params stay in
  ``cfg.param_dtype``. Attention logits/softmax in f32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig

Params = Dict[str, Any]
Axes = Dict[str, Any]


def shard_act(x: jax.Array, mesh, *, seq_axis: Optional[int] = 1):
    """Pin a (B, S, ...) activation's batch dim to the DP mesh axes.

    GSPMD's sharding propagation does not survive ``lax.scan`` while-loop
    boundaries without in-body constraints — unconstrained residual
    streams come out *batch-replicated* across the data axis (measured:
    16x redundant attention compute on llama3 train_4k; EXPERIMENTS.md
    §Perf iteration 1). Applied at every layer boundary.
    """
    if mesh is None:
        return x
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    if not dp:
        return x
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    if x.shape[0] % dp_size != 0:
        return x  # e.g. batch=1 long-context decode
    spec = [dp] + [None] * (x.ndim - 1)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


# logical dims that stay TP-sharded when a layer's weights are gathered
# (first matching dim wins — expert weights keep EP on the experts dim,
# their FFN dim replicates)
_TP_NAMES = ("experts", "qheads", "mlp", "vocab", "ssm_inner")


def gather_weights(lp, axes, mesh):
    """ZeRO-3 weight gather at the layer boundary: re-constrain every
    weight leaf to its TP-only sharding (FSDP 'embed' dim unsharded).

    Left to its own cost model, GSPMD keeps weights 2D-sharded and
    all-reduces f32 *activations* over the data axis instead (~247 GB/chip
    per llama3-8b train step — §Perf iteration 4). Applying the
    constraint inside the scan body makes the compiler all-gather each
    layer's bf16 weights once per direction, which is ~8x less traffic.
    """
    if mesh is None:
        return lp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    is_axes = lambda t: (isinstance(t, tuple)
                         and all(isinstance(s, str) for s in t))

    def one(w, ax):
        if ax and ax[0] == "layers":
            ax = ax[1:]  # the body sees a single layer slice
        if len(ax) != w.ndim or "model" not in mesh.axis_names:
            return w
        entries = []
        used = False
        for i, a in enumerate(ax):
            take = (not used and a in _TP_NAMES
                    and w.shape[i] % mesh.shape["model"] == 0)
            entries.append("model" if take else None)
            used = used or take
        spec = P(*entries)
        return jax.lax.with_sharding_constraint(w, NamedSharding(mesh, spec))

    leaves, treedef = jax.tree.flatten(lp)
    ax_leaves = treedef.flatten_up_to(axes)
    return treedef.unflatten([one(w, a) for w, a in zip(leaves, ax_leaves)])


# --------------------------------------------------------------------------
# init helpers
# --------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, in_name: str, out_name: str,
               dtype) -> Tuple[jax.Array, Tuple[str, str]]:
    scale = (2.0 / (in_dim + out_dim)) ** 0.5
    w = jax.random.normal(key, (in_dim, out_dim), dtype=jnp.float32) * scale
    return w.astype(dtype), (in_name, out_name)


def embed_init(key, vocab: int, dim: int, dtype):
    w = jax.random.normal(key, (vocab, dim), dtype=jnp.float32) * 0.02
    return w.astype(dtype), ("vocab", "embed")


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rmsnorm_init(dim: int, dtype):
    return jnp.ones((dim,), dtype=dtype), ("embed",)


def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * gamma.astype(dt)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for the even head dims (head_dim must be even)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]              # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float,
                sections: Tuple[int, int, int]) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    x: (B, S, H, hd); positions3: (3, B, S) temporal/height/width position
    streams. ``sections`` partitions the hd/2 frequency slots among the
    three streams (e.g. (16, 24, 24) for hd=128)."""
    import numpy as _np
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    # pick, per frequency slot, which position stream drives it (static)
    sec_ids = _np.repeat(_np.arange(3), _np.asarray(sections))  # (hd/2,)
    assert sec_ids.shape[0] == hd // 2, "mrope sections must sum to hd/2"
    pos = positions3.astype(jnp.float32)[sec_ids]       # (hd/2, B, S)
    angles = jnp.moveaxis(pos, 0, -1) * freqs           # (B, S, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# flash (blockwise) attention — forward-only prefill path
# --------------------------------------------------------------------------

def flash_sdpa(q: jax.Array, k: jax.Array, v: jax.Array, mesh, *,
               causal: bool = True) -> jax.Array:
    """Pallas blockwise attention for prefill (no S^2 HBM traffic).

    Heads stay TP-sharded: a shard_map wrapper gives every model-shard
    its query heads plus a dynamic slice of the (replicated) KV heads —
    contiguous GQA ordering makes each shard's heads span whole KV
    groups whenever H/tp divides G or vice versa. Falls back to the
    caller's jnp path when the head count does not tile (checked by the
    caller). Forward-only: the Pallas kernel has no VJP; training keeps
    the XLA attention, or :func:`splash_causal` where the family asks
    for it."""
    from jax.sharding import PartitionSpec as P

    from ..kernels.flash_attention.ops import flash_attention

    if mesh is None or "model" not in mesh.axis_names \
            or mesh.shape["model"] == 1:
        return flash_attention(q, k, v, causal=causal)

    B, S, H, hd = q.shape
    KV = k.shape[2]
    tp = mesh.shape["model"]
    H_loc = H // tp
    G = H // KV
    n_kv_loc = max(1, -(-H_loc // G))
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def local(qs, ks, vs):
        idx = jax.lax.axis_index("model")
        kv0 = (idx * H_loc) // G
        ks_l = jax.lax.dynamic_slice(
            ks, (0, 0, kv0, 0), ks.shape[:2] + (n_kv_loc, hd))
        vs_l = jax.lax.dynamic_slice(
            vs, (0, 0, kv0, 0), vs.shape[:2] + (n_kv_loc, hd))
        return flash_attention(qs, ks_l, vs_l, causal=causal)

    q_spec = P(dp, None, "model", None)
    kv_spec = P(dp, None, None, None)
    return jax.shard_map(local, mesh=mesh,
                         in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)(q, k, v)


def flash_applicable(cfg, q_heads: int, seq: int, mesh) -> bool:
    tp = mesh.shape["model"] if (mesh is not None
                                 and "model" in mesh.axis_names) else 1
    if q_heads % tp != 0 or seq % 8 != 0:
        return False
    H_loc = q_heads // tp
    G = q_heads // max(cfg.n_kv_heads, 1)
    return (H_loc % G == 0) or (G % H_loc == 0)


# --------------------------------------------------------------------------
# splash attention — causal training path on one TPU chip (nemotron_h)
# --------------------------------------------------------------------------

SPLASH_BLOCK = 512


def splash_applicable(seq: int, head_dim: int, mesh) -> bool:
    """JAX's splash attention kernel (forward and backward, no S^2 HBM
    tensor) runs where the default backend is a TPU, the layer is not
    sharded over several devices, and the sequence and head tile by 128
    lanes."""
    return (jax.default_backend() == "tpu"
            and (mesh is None or mesh.size == 1)
            and seq % 128 == 0 and head_dim % 128 == 0)


def splash_causal(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal softmax(q k^T / sqrt(hd)) v by splash attention, in its MQA
    form once per KV head (each shared by H / KV query heads, contiguous
    as ``jnp.repeat`` of the KV heads orders them). q: (B, S, H, hd);
    k, v: (B, S, KV, hd) -> (B, S, H, hd)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)

    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    blk = SPLASH_BLOCK if S % SPLASH_BLOCK == 0 else 128
    sizes = sk.BlockSizes(block_q=blk, block_kv=blk, block_kv_compute=blk,
                          block_q_dkv=blk, block_kv_dkv=blk,
                          block_kv_dkv_compute=blk, block_q_dq=blk,
                          block_kv_dq=blk)
    mask = sm.MultiHeadMask([sm.CausalMask((S, S)) for _ in range(G)])
    kernel = sk.make_splash_mqa_single_device(mask, block_sizes=sizes)
    # the kernel takes the logits unscaled
    qg = (q * (hd ** -0.5)).astype(q.dtype).reshape(B, S, KV, G, hd)
    qg = jnp.transpose(qg, (0, 2, 3, 1, 4))            # (B, KV, G, S, hd)
    kt = jnp.transpose(k, (0, 2, 1, 3))                 # (B, KV, S, hd)
    vt = jnp.transpose(v, (0, 2, 1, 3))
    out = jax.vmap(jax.vmap(kernel))(qg, kt, vt)        # (B, KV, G, S, hd)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, S, H, hd)


# --------------------------------------------------------------------------
# GQA attention
# --------------------------------------------------------------------------

def attention_init(cfg: ModelConfig, key) -> Tuple[Params, Axes]:
    D, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p, a = {}, {}
    p["wq"], a["wq"] = dense_init(ks[0], D, H * hd, "embed", "qheads", dtype)
    p["wk"], a["wk"] = dense_init(ks[1], D, KV * hd, "embed", "kvheads", dtype)
    p["wv"], a["wv"] = dense_init(ks[2], D, KV * hd, "embed", "kvheads", dtype)
    p["wo"], a["wo"] = dense_init(ks[3], H * hd, D, "qheads", "embed", dtype)
    return p, a


def _sdpa(q, k, v, *, causal: bool, q_pos0: int | jax.Array = 0,
          kv_len: Optional[jax.Array] = None):
    """Grouped dot-product attention.

    q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd). H % KV == 0. f32 softmax
    accumulation via preferred_element_type (no materialized f32 copies
    of q/k/v).

    GQA is computed by repeating KV heads up to H rather than splitting
    the H dim into (KV, G): H is TP-sharded, and reshaping a sharded dim
    into (KV, G) factors that do not divide the TP degree forces GSPMD
    into involuntary full rematerialization — batch-replicated S^2
    tensors (measured: 40x memory-term inflation on llama3 train_4k;
    EXPERIMENTS.md §Perf iteration 1).

    ``q_pos0``: absolute position of q[0] (decode offsets).
    ``kv_len``: valid prefix length of k/v (decode with preallocated cache).
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    Sk = k.shape[1]
    grouped = (kv_len is not None) and KV != H
    if grouped:
        # decode path: grouped einsum, never materialize the KV repeat
        # (repeating a 32k-token cache G-fold costs G x cache bytes per
        # step and triggers a full-cache kv-axis all-gather under TP —
        # §Perf iteration 6; decode runs with attention heads replicated
        # so the (KV, G) q reshape is shard-free).
        G = H // KV
        qg = q.reshape(B, Sq, KV, G, hd)
        logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                            preferred_element_type=jnp.float32) / (hd ** 0.5)
    else:
        if KV != H:  # train/prefill: repeat is S-bounded and TP-friendly
            k = jnp.repeat(k, H // KV, axis=2)
            v = jnp.repeat(v, H // KV, axis=2)
        logits = jnp.einsum("bqhd,bshd->bhqs", q, k,
                            preferred_element_type=jnp.float32) / (hd ** 0.5)
    if causal:
        qpos = q_pos0 + jnp.arange(Sq)
        kpos = jnp.arange(Sk)
        mask = kpos[None, :] <= qpos[:, None]           # (Sq, Sk)
        shape = (1, 1, 1) if grouped else (1, 1)
        logits = jnp.where(mask.reshape(shape + mask.shape), logits, -1e30)
    if kv_len is not None:
        valid = jnp.arange(Sk)[None, :] < jnp.asarray(kv_len).reshape(-1, 1)
        vshape = ((-1, 1, 1, 1, Sk) if grouped else (-1, 1, 1, Sk))
        logits = jnp.where(valid.reshape(vshape), logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if grouped:
        out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v,
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bhqs,bshd->bqhd", probs, v,
                         preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def attention_apply(cfg: ModelConfig, p: Params, x: jax.Array,
                    positions: jax.Array, *,
                    mrope_positions: Optional[jax.Array] = None,
                    cache: Optional[Dict[str, jax.Array]] = None,
                    cache_index: Optional[jax.Array] = None,
                    mesh=None, flash: bool = False, splash: bool = False):
    """Full attention. With ``cache`` (dict k/v (B, Smax, KV, hd)) performs
    one decode step: x is (B, 1, D), cache_index is the write position.
    ``flash`` (prefill) and ``splash`` (training) ask for the causal
    kernels where they apply; the caller's family chooses. Returns (out,
    new_cache)."""
    B, S, D = x.shape
    hd = cfg.resolved_head_dim
    H, KV = cfg.n_heads, cfg.n_kv_heads
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, H, hd)
    k = (x @ p["wk"].astype(x.dtype)).reshape(B, S, KV, hd)
    v = (x @ p["wv"].astype(x.dtype)).reshape(B, S, KV, hd)

    if cfg.mrope_sections:
        q = apply_mrope(q, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
    elif cfg.family != "audio" and cfg.use_rope:
        # (hubert's frontend embeds positions already)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = cache
    if cache is not None:
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, cache_index, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, cache_index, 0, 0))
        new_cache = {"k": k_cache, "v": v_cache}
        out = _sdpa(q, k_cache, v_cache, causal=False,
                    kv_len=cache_index + S)
    elif flash and cfg.causal and flash_applicable(cfg, H, S, mesh):
        # Pallas blockwise attention: prefill only (forward-only kernel)
        out = flash_sdpa(q, k, v, mesh, causal=True)
    elif splash and cfg.causal and splash_applicable(S, hd, mesh):
        out = splash_causal(q, k, v)
    else:
        out = _sdpa(q, k, v, causal=cfg.causal)
    out = out.reshape(B, S, H * hd)
    return out @ p["wo"].astype(x.dtype), new_cache


def attention_cache_init(cfg: ModelConfig, batch: int, max_len: int):
    """(cache pytree, axes) for one attention layer."""
    hd = cfg.resolved_head_dim
    dtype = jnp.dtype(cfg.compute_dtype)
    shape = (batch, max_len, cfg.n_kv_heads, hd)
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    ax = ("batch", "seq_cache", "kvheads_sep", "head_dim")
    return cache, {"k": ax, "v": ax}


# --------------------------------------------------------------------------
# SwiGLU FFN
# --------------------------------------------------------------------------

def swiglu_init(cfg: ModelConfig, key, d_ff: Optional[int] = None
                ) -> Tuple[Params, Axes]:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    p, a = {}, {}
    p["w_gate"], a["w_gate"] = dense_init(ks[0], D, F, "embed", "mlp", dtype)
    p["w_up"], a["w_up"] = dense_init(ks[1], D, F, "embed", "mlp", dtype)
    p["w_down"], a["w_down"] = dense_init(ks[2], F, D, "mlp", "embed", dtype)
    return p, a


def swiglu_apply(p: Params, x: jax.Array) -> jax.Array:
    dt = x.dtype
    gate = jax.nn.silu(x @ p["w_gate"].astype(dt))
    up = x @ p["w_up"].astype(dt)
    return (gate * up) @ p["w_down"].astype(dt)
