"""Mamba2 / SSD (state-space duality) block — arXiv:2405.21060.

Training path uses the chunked SSD formulation: within a chunk the
recurrence is materialized as a masked (semiseparable) attention-like
matmul — MXU-friendly — and across chunks a tiny ``lax.scan`` carries the
(heads, head_dim, state) SSM state. Decode is the O(1)-per-token
recurrent update — the reason the long_500k shape is runnable for the
ssm/hybrid archs and skipped for full-attention ones.

Layout follows the reference Mamba2: in_proj emits [z | x | B | C | dt],
depthwise causal conv (width 4) over [x | B | C], scalar-per-head decay
A, head-wise dt, D skip, SiLU(z) gate, out_proj. The inner width is
``cfg.ssm_inner`` (heads x head_dim where the config gives the heads).
B and C come in ``cfg.ssm_groups`` groups of ``ssm_state``: head j reads
the group j // (heads / groups). With ``cfg.ssm_gated_norm`` the gated
output ``y * silu(z)`` is RMS-normalized over each group's
``inner / groups`` channels and scaled by a learned weight before
out_proj (NemotronH's ``MambaRMSNormGated``); without it the gate goes
straight to out_proj, as in mamba2-130m.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import Axes, Params, dense_init

__all__ = ["mamba2_init", "mamba2_apply", "mamba2_decode_step",
           "mamba2_cache_init", "mamba2_dims"]


def mamba2_dims(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(d_inner, n_heads, conv_channels)."""
    d_inner = cfg.ssm_inner
    nheads = d_inner // cfg.ssm_head_dim
    # x, B, C get convolved
    conv_ch = d_inner + 2 * cfg.ssm_groups * cfg.ssm_state
    return d_inner, nheads, conv_ch


def mamba2_init(cfg: ModelConfig, key) -> Tuple[Params, Axes]:
    D = cfg.d_model
    d_inner, nheads, conv_ch = mamba2_dims(cfg)
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p, a = {}, {}
    d_proj = d_inner + conv_ch + nheads  # z, x, B, C, dt
    p["in_proj"], a["in_proj"] = dense_init(ks[0], D, d_proj,
                                            "embed", "ssm_proj", dtype)
    p["conv_w"] = (jax.random.normal(ks[1], (cfg.ssm_conv_width, conv_ch),
                                     jnp.float32) * 0.1).astype(dtype)
    a["conv_w"] = ("conv_width", "ssm_conv")
    p["conv_b"] = jnp.zeros((conv_ch,), dtype)
    a["conv_b"] = ("ssm_conv",)
    # A in (-exp) parameterization, one scalar per head; dt bias for softplus
    p["A_log"] = jnp.log(jnp.linspace(1.0, 16.0, nheads)).astype(jnp.float32)
    a["A_log"] = ("ssm_heads",)
    p["dt_bias"] = jnp.full((nheads,), 0.5, jnp.float32)
    a["dt_bias"] = ("ssm_heads",)
    p["D_skip"] = jnp.ones((nheads,), jnp.float32)
    a["D_skip"] = ("ssm_heads",)
    if cfg.ssm_gated_norm:
        p["norm"] = jnp.ones((d_inner,), dtype)
        a["norm"] = ("ssm_inner",)
    p["out_proj"], a["out_proj"] = dense_init(ks[4], d_inner, D,
                                              "ssm_inner", "embed", dtype)
    return p, a


def _split_proj(cfg: ModelConfig, proj: jax.Array):
    d_inner, nheads, _ = mamba2_dims(cfg)
    N = cfg.ssm_groups * cfg.ssm_state
    z, xs, B, C, dt = jnp.split(
        proj, [d_inner, 2 * d_inner, 2 * d_inner + N, 2 * d_inner + 2 * N],
        axis=-1)
    return z, xs, B, C, dt


def _causal_conv(u: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. u: (B, S, C); w: (W, C)."""
    W = w.shape[0]
    pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    out = jnp.zeros_like(u)
    for i in range(W):  # W=4: tiny unroll, fuses into one vectorized op
        out = out + pad[:, i:i + u.shape[1], :] * w[i]
    return jax.nn.silu(out + b)


def _segsum(log_a: jax.Array) -> jax.Array:
    """Lower-triangular pairwise cumulative sums: out[..., i, j] =
    sum_{j < u <= i} log_a[..., u], -inf above the diagonal."""
    Q = log_a.shape[-1]
    cs = jnp.cumsum(log_a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]          # (..., i, j)
    mask = jnp.tril(jnp.ones((Q, Q), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def _gate(cfg: ModelConfig, p: Params, y: jax.Array, z: jax.Array):
    """``y * silu(z)``, then with ``ssm_gated_norm`` the group-wise RMSNorm
    (in float32, gate included) and its weight. y, z: (..., d_inner)."""
    if not cfg.ssm_gated_norm:
        return y * jax.nn.silu(z)
    dt = y.dtype
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = g.reshape(g.shape[:-1] + (cfg.ssm_groups, -1))
    var = jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
    g = (grouped * jax.lax.rsqrt(var + cfg.norm_eps)).reshape(g.shape)
    return g.astype(dt) * p["norm"].astype(dt)


def _ssd_grouped(Cc, Bc, xdt, la, G: int):
    """The chunked SSD of :func:`mamba2_apply` where head j reads B/C
    group j // (H / G). Cc, Bc: (B,nc,Q,G,N); xdt: (B,nc,Q,H,P); la:
    (B,nc,Q,H) -> y without the D skip: (B,nc,Q,H,P)."""
    Bb, nc, Q, H, hd = xdt.shape
    N = Bc.shape[-1]
    Hg = H // G
    xg = xdt.reshape(Bb, nc, Q, G, Hg, hd)
    L = jnp.exp(_segsum(jnp.moveaxis(la, -1, -2)))   # (B,nc,H,Q,Q)
    L = L.reshape(Bb, nc, G, Hg, Q, Q)
    scores = jnp.einsum("bcqgn,bckgn->bcgqk", Cc, Bc)
    y_intra = jnp.einsum("bcghqk,bcgqk,bckghp->bcqghp", L, scores, xg)

    la_cum = jnp.cumsum(la, axis=2)                  # (B,nc,Q,H)
    la_tot = la_cum[:, :, -1, :]                     # (B,nc,H)
    decay_to_end = jnp.exp(la_tot[:, :, None, :] - la_cum)
    S_c = jnp.einsum("bcqgn,bcqgh,bcqghp->bcghpn", Bc,
                     decay_to_end.reshape(Bb, nc, Q, G, Hg), xg)
    S_c = S_c.reshape(Bb, nc, H, hd, N)

    def scan_fn(state, inp):
        s_c, tot = inp
        return state * jnp.exp(tot)[:, :, None, None] + s_c, state

    init = jnp.zeros((Bb, H, hd, N), jnp.float32)
    _, states_in = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(S_c, 1, 0), jnp.moveaxis(la_tot, 1, 0)))
    states_in = jnp.moveaxis(states_in, 0, 1).reshape(Bb, nc, G, Hg, hd, N)
    y_inter = jnp.einsum("bcqgn,bcqgh,bcghpn->bcqghp", Cc,
                         jnp.exp(la_cum).reshape(Bb, nc, Q, G, Hg),
                         states_in)
    return (y_intra + y_inter).reshape(Bb, nc, Q, H, hd)


def mamba2_apply(cfg: ModelConfig, p: Params, x_in: jax.Array) -> jax.Array:
    """Full-sequence SSD. x_in: (B, S, D) -> (B, S, D). S % chunk == 0
    (callers pad; all assigned shapes are powers of two)."""
    Bb, S, D = x_in.shape
    N = cfg.ssm_state
    G = cfg.ssm_groups
    Q = min(cfg.ssm_chunk, S)
    assert S % Q == 0, (S, Q)
    d_inner, nheads, _ = mamba2_dims(cfg)
    hd = cfg.ssm_head_dim
    dt_ = x_in.dtype

    proj = x_in @ p["in_proj"].astype(dt_)
    z, xs, Bm, Cm, dt = _split_proj(cfg, proj)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)
    conv_out = _causal_conv(conv_in, p["conv_w"].astype(dt_),
                            p["conv_b"].astype(dt_))
    xs, Bm, Cm = jnp.split(conv_out, [d_inner, d_inner + G * N], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,S,H)
    A = -jnp.exp(p["A_log"])                                      # (H,)
    log_a = dt * A[None, None, :]                                 # (B,S,H)

    nc = S // Q
    xh = xs.reshape(Bb, nc, Q, nheads, hd).astype(jnp.float32)
    bc_shape = (Bb, nc, Q) + ((G, N) if G > 1 else (N,))
    Bc = Bm.reshape(bc_shape).astype(jnp.float32)
    Cc = Cm.reshape(bc_shape).astype(jnp.float32)
    la = log_a.reshape(Bb, nc, Q, nheads)
    dtc = dt.reshape(Bb, nc, Q, nheads)
    xdt = xh * dtc[..., None]                                     # fold dt in
    if G > 1:
        y = _ssd_grouped(Cc, Bc, xdt, la, G).reshape(Bb, S, nheads, hd)
        y = y + xh.reshape(Bb, S, nheads, hd) * p["D_skip"][None, None, :,
                                                            None]
        y = _gate(cfg, p, y.reshape(Bb, S, d_inner).astype(dt_), z)
        return y @ p["out_proj"].astype(dt_)

    # ---- intra-chunk (quadratic within chunk, MXU matmuls) ---------------
    L = jnp.exp(_segsum(jnp.moveaxis(la, -1, -2)))   # (B,nc,H,Q,Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)   # (B,nc,Q,Q)
    y_intra = jnp.einsum("bchqk,bcqk,bckhp->bcqhp",
                         L, scores, xdt)

    # ---- chunk summaries + inter-chunk scan ------------------------------
    la_cum = jnp.cumsum(la, axis=2)                  # (B,nc,Q,H)
    la_tot = la_cum[:, :, -1, :]                     # (B,nc,H)
    decay_to_end = jnp.exp(la_tot[:, :, None, :] - la_cum)  # (B,nc,Q,H)
    # state contribution of each chunk: (B,nc,H,hd,N)
    S_c = jnp.einsum("bcqn,bcqh,bcqhp->bchpn", Bc, decay_to_end, xdt)

    def scan_fn(state, inp):
        s_c, tot = inp                                # (B,H,hd,N), (B,H)
        new = state * jnp.exp(tot)[:, :, None, None] + s_c
        return new, state                             # emit state *entering*

    init = jnp.zeros((Bb, nheads, hd, N), jnp.float32)
    _, states_in = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(S_c, 1, 0), jnp.moveaxis(la_tot, 1, 0)))
    states_in = jnp.moveaxis(states_in, 0, 1)         # (B,nc,H,hd,N)

    # inter-chunk output: C_t · decay(t) · state_in
    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp",
                         Cc, jnp.exp(la_cum), states_in)

    y = (y_intra + y_inter).reshape(Bb, S, nheads, hd)
    y = y + xh.reshape(Bb, S, nheads, hd) * p["D_skip"][None, None, :, None]
    y = y.reshape(Bb, S, d_inner).astype(dt_)
    y = _gate(cfg, p, y, z)
    return y @ p["out_proj"].astype(dt_)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def mamba2_cache_init(cfg: ModelConfig, batch: int):
    """SSM state + conv tail. O(1) in sequence length."""
    d_inner, nheads, conv_ch = mamba2_dims(cfg)
    dtype = jnp.float32
    cache = {
        "state": jnp.zeros((batch, nheads, cfg.ssm_head_dim, cfg.ssm_state),
                           dtype),
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_ch),
                          jnp.dtype(cfg.compute_dtype)),
    }
    axes = {
        "state": ("batch", "ssm_heads", "head_dim", "state"),
        "conv": ("batch", "conv_width", "ssm_conv"),
    }
    return cache, axes


def mamba2_decode_step(cfg: ModelConfig, p: Params, x_tok: jax.Array,
                       cache: Dict[str, jax.Array]):
    """One token. x_tok: (B, 1, D) -> ((B, 1, D), new cache)."""
    Bb = x_tok.shape[0]
    N = cfg.ssm_state
    G = cfg.ssm_groups
    d_inner, nheads, conv_ch = mamba2_dims(cfg)
    hd = cfg.ssm_head_dim
    dt_ = x_tok.dtype

    proj = (x_tok[:, 0, :] @ p["in_proj"].astype(dt_))
    z, xs, Bm, Cm, dt = _split_proj(cfg, proj)
    conv_in = jnp.concatenate([xs, Bm, Cm], axis=-1)   # (B, conv_ch)
    window = jnp.concatenate([cache["conv"],
                              conv_in[:, None, :].astype(cache["conv"].dtype)],
                             axis=1)                   # (B, W, conv_ch)
    w = p["conv_w"].astype(dt_)
    conv_out = jax.nn.silu(
        jnp.einsum("bwc,wc->bc", window.astype(dt_), w)
        + p["conv_b"].astype(dt_))
    xs, Bm, Cm = jnp.split(conv_out, [d_inner, d_inner + G * N], axis=-1)
    if G > 1:   # each head reads its group's B and C
        per_head = lambda m: jnp.repeat(m.reshape(Bb, G, N), nheads // G,
                                        axis=1)
        Bm, Cm = per_head(Bm), per_head(Cm)

    dt_h = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,H)
    A = -jnp.exp(p["A_log"])
    da = jnp.exp(dt_h * A[None, :])                                # (B,H)
    xh = xs.reshape(Bb, nheads, hd).astype(jnp.float32)
    bn = "bhn" if G > 1 else "bn"
    state = (cache["state"] * da[:, :, None, None]
             + jnp.einsum(f"bhp,{bn},bh->bhpn", xh, Bm.astype(jnp.float32),
                          dt_h))
    y = jnp.einsum(f"bhpn,{bn}->bhp", state, Cm.astype(jnp.float32))
    y = y + xh * p["D_skip"][None, :, None]
    y = _gate(cfg, p, y.reshape(Bb, d_inner).astype(dt_), z)
    out = (y @ p["out_proj"].astype(dt_))[:, None, :]
    new_cache = {"state": state, "conv": window[:, 1:, :]}
    return out, new_cache
