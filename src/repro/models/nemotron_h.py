"""NemotronH hybrid stack (the ``nemotron_h`` family: NVIDIA Nemotron-H and
Nemotron 3 Nano): one pre-norm residual block per letter of
``cfg.layer_pattern``,

    h <- h + mixer_c(RMSNorm(h))     c in {M: Mamba-2, *: attention, E: MoE}

then the final RMSNorm and an untied head. The Mamba-2 mixer is
``models/mamba2.py`` with grouped B/C and the gated group-wise RMSNorm;
attention is GQA without rotation (``cfg.use_rope`` off), by splash
attention on one TPU chip (``layers.splash_causal``); the MoE mixer
is ``models/moe.py``'s held-experts layer (this chip's experts of the
router's ``n_experts``, plus the shared expert). The blocks differ in
kind, so they are unrolled (a list of parameter dicts), each under the
remat policy. Every mixer runs under a named scope (``mamba``,
``attention``, and ``moe.router`` / ``moe.experts`` / ``moe.shared``
inside the MoE layer), so the device's operations carry stable names.

``loss_fn`` returns the loss and the MoE layers' routing counts:
``moe_rows`` (MoE layers, held experts) and ``moe_overflow`` (MoE layers).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers as L
from . import mamba2 as M
from . import moe as MOE
from .lm import cross_entropy

__all__ = ["init", "abstract_init", "forward", "loss_fn", "init_cache",
           "decode_step"]

MIXERS = {"M": "mamba", "*": "attn", "E": "moe"}


def _block_init(cfg: ModelConfig, letter: str, key):
    p, a = {}, {}
    p["norm"], a["norm"] = L.rmsnorm_init(cfg.d_model,
                                          jnp.dtype(cfg.param_dtype))
    init = {"M": M.mamba2_init, "*": L.attention_init,
            "E": MOE.moe_held_init}[letter]
    p[MIXERS[letter]], a[MIXERS[letter]] = init(cfg, key)
    return p, a


def init(cfg: ModelConfig, key) -> Tuple[Dict, Dict]:
    if (len(cfg.layer_pattern) != cfg.n_layers
            or not set(cfg.layer_pattern) <= set(MIXERS)):
        raise ValueError(f"layer_pattern {cfg.layer_pattern!r}: one of "
                         f"{''.join(MIXERS)} per layer, {cfg.n_layers} "
                         f"layers")
    k_emb, k_head, *ks = jax.random.split(key, 2 + cfg.n_layers)
    dtype = jnp.dtype(cfg.param_dtype)
    p, a = {}, {}
    p["embed"], a["embed"] = L.embed_init(k_emb, cfg.padded_vocab,
                                          cfg.d_model, dtype)
    blocks = [_block_init(cfg, c, k) for c, k in zip(cfg.layer_pattern, ks)]
    p["blocks"] = [b[0] for b in blocks]
    a["blocks"] = [b[1] for b in blocks]
    p["norm_f"], a["norm_f"] = L.rmsnorm_init(cfg.d_model, dtype)
    p["head"], a["head"] = L.dense_init(k_head, cfg.d_model, cfg.padded_vocab,
                                        "embed", "vocab", dtype)
    return p, a


def abstract_init(cfg: ModelConfig, key):
    box = {}

    def params_only(k):
        prms, axes = init(cfg, k)
        box["axes"] = axes
        return prms

    return jax.eval_shape(params_only, key), box["axes"]


def _mixer(cfg: ModelConfig, letter: str, p: Dict, x: jax.Array, mesh):
    """(mixer output, counts or None) for the normed input x (B,S,D)."""
    if letter == "M":
        with jax.named_scope("mamba"):
            return M.mamba2_apply(cfg, p, x), None
    if letter == "*":
        with jax.named_scope("attention"):
            positions = jnp.arange(x.shape[1])[None]
            return L.attention_apply(cfg, p, x, positions, mesh=mesh,
                                     splash=True)[0], None
    y, counts = MOE.moe_held_apply(cfg, p, x.reshape(-1, x.shape[-1]))
    return y.reshape(x.shape), counts


def _checkpointed(fn, remat: str):
    if remat == "full":
        return jax.checkpoint(fn,
                              policy=jax.checkpoint_policies.nothing_saveable)
    if remat == "dots":
        return jax.checkpoint(fn, policy=(
            jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims))
    return fn


def forward(cfg: ModelConfig, params: Dict, batch: Dict, mesh=None,
            remat: str = "none"):
    """-> (logits (B,S,vocab), counts)."""
    dt = jnp.dtype(cfg.compute_dtype)
    h = jnp.take(params["embed"], batch["tokens"], axis=0).astype(dt)
    rows, overflow = [], []
    for c, bp in zip(cfg.layer_pattern, params["blocks"]):
        def block(h, bp, c=c):
            h = L.shard_act(h, mesh)
            y, counts = _mixer(cfg, c, bp[MIXERS[c]],
                               L.rmsnorm(h, bp["norm"], cfg.norm_eps), mesh)
            return L.shard_act(h + y, mesh), counts
        h, counts = _checkpointed(block, remat)(h, bp)
        if counts is not None:
            rows.append(counts["rows"])
            overflow.append(counts["overflow"])
    counts = {}
    if rows:
        counts = {"moe_rows": jnp.stack(rows),
                  "moe_overflow": jnp.stack(overflow)}
    h = L.rmsnorm(h, params["norm_f"], cfg.norm_eps)
    logits = h @ params["head"].astype(h.dtype)
    return logits[..., :cfg.vocab_size], counts


def loss_fn(cfg: ModelConfig, params: Dict, batch: Dict, mesh=None,
            remat: str = "none"):
    """-> (mean next-token cross entropy, counts)."""
    logits, counts = forward(cfg, params, batch, mesh, remat=remat)
    return cross_entropy(logits, batch["labels"]), counts


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Per block: the Mamba-2 state and conv tail, the attention KV cache,
    nothing for an MoE block."""
    caches, axes = [], []
    for c in cfg.layer_pattern:
        if c == "M":
            one, ax = M.mamba2_cache_init(cfg, batch)
        elif c == "*":
            one, ax = L.attention_cache_init(cfg, batch, max_len)
        else:
            one, ax = {}, {}
        caches.append(one)
        axes.append(ax)
    return caches, axes


def decode_step(cfg: ModelConfig, params: Dict, cache, tokens: jax.Array,
                pos: jax.Array, mesh=None):
    """One token per row. tokens: (B, 1) -> ((B, 1, vocab), new cache)."""
    dt = jnp.dtype(cfg.compute_dtype)
    h = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    new_cache = []
    for c, bp, lc in zip(cfg.layer_pattern, params["blocks"], cache):
        x = L.rmsnorm(h, bp["norm"], cfg.norm_eps)
        if c == "M":
            y, lc = M.mamba2_decode_step(cfg, bp["mamba"], x, lc)
        elif c == "*":
            positions = jnp.full((1, 1), pos)
            y, lc = L.attention_apply(cfg, bp["attn"], x, positions,
                                      cache=lc, cache_index=pos, mesh=mesh)
        else:
            y, _ = MOE.moe_held_apply(cfg, bp["moe"],
                                      x.reshape(-1, x.shape[-1]))
            y = y.reshape(x.shape)
        h = h + y
        new_cache.append(lc)
    h = L.rmsnorm(h, params["norm_f"], cfg.norm_eps)
    logits = h @ params["head"].astype(h.dtype)
    return logits[..., :cfg.vocab_size], new_cache
