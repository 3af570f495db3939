"""Plain float32 reference of one chip's share of NVIDIA Nemotron 3 Nano
30B-A3B (``nemotron_h``) and its loss.

Written from the published configuration and the NemotronH modeling code
in straightforward ``jax.numpy``. Block i with letter c_i of the pattern
computes ``h <- h + mixer_c(RMSNorm_i(h))``; then the final RMSNorm, an
untied head over the held vocabulary rows and the mean next-token cross
entropy. The mixers:

* M, Mamba-2: ``in_proj`` gives ``[z | xBC | dt]``; a depthwise causal
  convolution with bias and SiLU over xBC; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; the state recurrence stepped one position at a time,

      h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t

  where head j reads the B and C of group j // (heads / groups); then
  ``y * silu(z)`` RMS-normalized over each group's channels, times the
  norm's weight, and ``out_proj``.
* ``*``, attention: q, k, v projections, causal softmax(q k^T / sqrt(hd)) v
  with each KV head shared by heads / KV query heads, no rotation, o
  projection. Computed in blocks of query positions so that it fits at
  8,192 positions.
* E, MoE: sigmoid of the float32 router logits; the top-k of the scores
  plus the correction bias chooses the experts; their scores,
  renormalized and times the routed scaling, weigh them. Each held expert
  ``relu(x W_up)^2 W_down`` is computed densely on every token and masked
  by the routing; the absent experts add nothing (they lie on other
  chips). Plus the shared expert, on every token.

It imports nothing of the system under test.

Departures from the published model, each one made because this chip's
share is what the system under test computes:

* 7 of the 52 blocks (``MEMEM*E``), experts [offset, offset + held) of
  128, and the held vocabulary rows (see the configuration's
  ``deployment``);
* ``e_score_correction_bias`` is drawn from the seed (the published
  initialization zeros it) and gets no load-balancing update; it gets no
  gradient, and AdamW treats it as every leaf;
* no ``dt`` clamp and no ``time_step_floor`` beyond the initialization
  (the published training path has none either).

The residual stream is float32 throughout. Matrix products run at
``Precision.HIGHEST``. A ``operand_dtype`` rounds the operands of every
projection (the router's included) and of the head to a lower precision
first; that is the control of the comparison (float8 where the
configuration computes in bfloat16).

Every block is rematerialized; the Mamba-2 mixer runs one sequence at a
time and its scan keeps its state only every ``SCAN_BLOCK`` positions,
and attention and the head recompute each block of query positions or
tokens in the backward pass, so that the backward pass fits one chip
beside the AdamW state at the timed size.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# positions between the states the scan keeps for its backward pass
SCAN_BLOCK = 64
# query positions per attention block
QUERY_BLOCK = 128
# tokens per block of the head and the loss
TOKEN_BLOCK = 2048


class Dims(NamedTuple):
    pattern: str
    d_model: int
    d_inner: int
    heads: int
    head_dim: int
    groups: int
    state: int
    conv: int
    q_heads: int
    kv_heads: int
    attn_dim: int
    experts: int     # the router's width
    held: int
    offset: int
    top_k: int
    ffn: int
    shared_ffn: int
    scaling: float
    vocab: int
    rows: int        # embedding rows (vocab padded to a multiple of 256)
    eps: float


def dims(config: dict) -> Dims:
    m = config["model"]
    vocab = m["vocab_size"]
    return Dims(pattern=m["layer_pattern"], d_model=m["d_model"],
                d_inner=m["ssm_heads"] * m["ssm_head_dim"],
                heads=m["ssm_heads"], head_dim=m["ssm_head_dim"],
                groups=m["ssm_groups"], state=m["ssm_state"],
                conv=m["ssm_conv_width"], q_heads=m["n_heads"],
                kv_heads=m["n_kv_heads"], attn_dim=m["head_dim"],
                experts=m["n_experts"],
                held=m["experts_held"] or m["n_experts"],
                offset=m["expert_offset"], top_k=m["experts_per_token"],
                ffn=m["moe_d_ff"], shared_ffn=m["shared_d_ff"],
                scaling=m["routed_scaling"], vocab=vocab,
                rows=-(-vocab // 256) * 256, eps=m["norm_eps"])


def _normal(k, shape, std):
    return std * jax.random.normal(k, shape, jnp.float32)


def _mamba_init(d: Dims, k, residual_scale: float) -> dict:
    D, E, H, W = d.d_model, d.d_inner, d.heads, d.conv
    conv_ch = E + 2 * d.groups * d.state
    ks = jax.random.split(k, 5)
    dt = jnp.exp(jax.random.uniform(ks[3], (H,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    bound = 1.0 / math.sqrt(W)
    return {
        "in_proj": _normal(ks[0], (D, E + conv_ch + H), 0.02),
        "conv_w": jax.random.uniform(ks[1], (W, conv_ch), jnp.float32,
                                     -bound, bound),
        "conv_b": jax.random.uniform(ks[2], (conv_ch,), jnp.float32,
                                     -bound, bound),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "D_skip": jnp.ones((H,), jnp.float32),
        "norm": jnp.ones((E,), jnp.float32),
        "out_proj": _normal(ks[4], (E, D), 0.02) * residual_scale,
    }


def _attn_init(d: Dims, k, residual_scale: float) -> dict:
    D, hd = d.d_model, d.attn_dim
    ks = jax.random.split(k, 4)
    return {"wq": _normal(ks[0], (D, d.q_heads * hd), 0.02),
            "wk": _normal(ks[1], (D, d.kv_heads * hd), 0.02),
            "wv": _normal(ks[2], (D, d.kv_heads * hd), 0.02),
            "wo": _normal(ks[3], (d.q_heads * hd, D), 0.02) * residual_scale}


def _moe_init(d: Dims, k, residual_scale: float) -> dict:
    D = d.d_model
    ks = jax.random.split(k, 6)
    return {
        "router": _normal(ks[0], (D, d.experts), 0.02),
        "bias": jax.random.uniform(ks[1], (d.experts,), jnp.float32,
                                   -0.05, 0.05),
        "w_up": _normal(ks[2], (d.held, D, d.ffn), 0.02),
        "w_down": _normal(ks[3], (d.held, d.ffn, D), 0.02) * residual_scale,
        "shared": {
            "w_up": _normal(ks[4], (D, d.shared_ffn), 0.02),
            "w_down": _normal(ks[5], (d.shared_ffn, D), 0.02)
            * residual_scale,
        },
    }


def init_params(config: dict, key) -> dict:
    """Weights drawn from ``key``: N(0, 0.02) for every projection (the
    published ``initializer_range``), the projections into the residual
    stream scaled by 1/sqrt(blocks) (``rescale_prenorm_residual``),
    PyTorch's default uniform for the convolution, ``A = 1..heads``,
    ``dt ~ logU[1e-3, 1e-1]`` stored as its inverse softplus, ``D = 1``,
    unit norms. The tree has the layout the trainer's parameters have."""
    d = dims(config)
    D = d.d_model
    ks = jax.random.split(key, 2 + len(d.pattern))
    scale = 1.0 / math.sqrt(len(d.pattern))
    make = {"M": ("mamba", _mamba_init), "*": ("attn", _attn_init),
            "E": ("moe", _moe_init)}
    blocks = []
    for c, k in zip(d.pattern, ks[2:]):
        name, fn = make[c]
        blocks.append({"norm": jnp.ones((D,), jnp.float32),
                       name: fn(d, k, scale)})
    return {
        "embed": _normal(ks[0], (d.rows, D), 0.02),
        "blocks": blocks,
        "norm_f": jnp.ones((D,), jnp.float32),
        "head": _normal(ks[1], (D, d.rows), 0.02),
    }


def _round(x, operand_dtype):
    if operand_dtype is None:
        return x
    return x.astype(operand_dtype).astype(jnp.float32)


def _matmul(a, b, operand_dtype):
    return jnp.matmul(_round(a, operand_dtype), _round(b, operand_dtype),
                      precision=HIGHEST)


def _rmsnorm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gamma


def _ssm(x, dt, A, B, C, block):
    """Sequential recurrence. x: (b, S, H, P); dt: (b, S, H); A: (H,);
    B, C: (b, S, G, N); head j reads group j // (H / G) -> y: (b, S, H, P)
    without the D skip."""
    b, S, H, P = x.shape
    G, N = B.shape[-2:]

    def step(h, inp):
        xt, dtt, bt, ct = inp
        bt, ct = (jnp.repeat(m, H // G, axis=1) for m in (bt, ct))
        h = (h * jnp.exp(dtt * A)[:, :, None, None]
             + (dtt[:, :, None] * xt)[..., None] * bt[:, :, None, :])
        return h, jnp.einsum("bhpn,bhn->bhp", h, ct, precision=HIGHEST)

    @jax.checkpoint
    def run_block(h, inp):
        return jax.lax.scan(step, h, inp)

    def time_major(a):
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((S // block, block) + a.shape[1:])

    h0 = jnp.zeros((b, H, P, N), jnp.float32)
    _, y = jax.lax.scan(run_block, h0,
                        tuple(time_major(a) for a in (x, dt, B, C)))
    return jnp.moveaxis(y.reshape((S,) + y.shape[2:]), 0, 1)


def _mamba(d: Dims, p: dict, u, operand_dtype, block):
    """One sequence of the batch at a time, each recomputed in the
    backward pass."""
    E, conv_ch = d.d_inner, d.d_inner + 2 * d.groups * d.state
    w = p["in_proj"]

    @jax.checkpoint
    def one(u):
        u = u[None]
        z = _matmul(u, w[:, :E], operand_dtype)
        xbc = _matmul(u, w[:, E:E + conv_ch], operand_dtype)
        dt = _matmul(u, w[:, E + conv_ch:], operand_dtype)
        return _matmul(_mixed(d, p, z, xbc, dt, block), p["out_proj"],
                       operand_dtype)[0]

    return jax.lax.map(one, u)


@functools.partial(jax.checkpoint, static_argnums=(0, 5))
def _mixed(d: Dims, p: dict, z, xbc, dt, block):
    """The convolution, the recurrence and the gated norm of a Mamba-2
    block, from in_proj's three parts."""
    b, S, _ = z.shape
    E, N, H, P, G = d.d_inner, d.state, d.heads, d.head_dim, d.groups
    W = p["conv_w"].shape[0]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = p["conv_b"] + sum(pad[:, k:k + S, :] * p["conv_w"][k]
                             for k in range(W))
    xbc = jax.nn.silu(conv)
    x = xbc[..., :E].reshape(b, S, H, P)
    B = xbc[..., E:E + G * N].reshape(b, S, G, N)
    C = xbc[..., E + G * N:].reshape(b, S, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = _ssm(x, dt, A, B, C, block) + x * p["D_skip"][:, None]
    y = y.reshape(b, S, E) * jax.nn.silu(z)
    yg = y.reshape(b, S, G, E // G)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                            + d.eps)
    return yg.reshape(b, S, E) * p["norm"]


def _attention(d: Dims, p: dict, u, operand_dtype):
    b, S, _ = u.shape
    H, KV, hd = d.q_heads, d.kv_heads, d.attn_dim
    # query head j reads KV head j // (H / KV)
    q = _matmul(u, p["wq"], operand_dtype).reshape(b, S, KV, H // KV, hd)
    k = _matmul(u, p["wk"], operand_dtype).reshape(b, S, KV, hd)
    v = _matmul(u, p["wv"], operand_dtype).reshape(b, S, KV, hd)
    qb = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    @jax.checkpoint
    def one_block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qi, k,
                            precision=HIGHEST) / math.sqrt(hd)
        pos = i * qb + jnp.arange(qb)
        mask = jnp.arange(S)[None, :] <= pos[:, None]
        probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", probs, v, precision=HIGHEST)

    out = jax.lax.map(one_block, jnp.arange(S // qb))  # (nb, b, qb, KV, G, hd)
    out = jnp.moveaxis(out, 0, 1).reshape(b, S, H * hd)
    return _matmul(out, p["wo"], operand_dtype)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def routing(d: Dims, p: dict, x, operand_dtype=None):
    """(weights (T, K), ids (T, K)) over all the router's experts."""
    scores = jax.nn.sigmoid(_matmul(x, p["router"], operand_dtype))
    _, ids = jax.lax.top_k(scores + p["bias"], d.top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    return d.scaling * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20), ids


def held_experts(d: Dims, p: dict, x, operand_dtype=None):
    """The held experts' part of the MoE layer for tokens x (T, D)."""
    T = x.shape[0]
    w, ids = routing(d, p, x, operand_dtype)
    combine = jnp.zeros((T, d.experts), jnp.float32).at[
        jnp.arange(T)[:, None], ids].add(w)
    combine = combine[:, d.offset:d.offset + d.held]      # (T, held)

    @jax.checkpoint
    def expert(acc, inp):
        up, down, c = inp
        h = _relu2(_matmul(x, up, operand_dtype))
        return acc + c[:, None] * _matmul(h, down, operand_dtype), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (p["w_up"], p["w_down"], combine.T))
    return out


def shared_expert(p: dict, x, operand_dtype=None):
    return _matmul(_relu2(_matmul(x, p["w_up"], operand_dtype)),
                   p["w_down"], operand_dtype)


def _moe(d: Dims, p: dict, u, operand_dtype):
    x = u.reshape(-1, u.shape[-1])
    out = (held_experts(d, p, x, operand_dtype)
           + shared_expert(p["shared"], x, operand_dtype))
    return out.reshape(u.shape)


def _nll_sum(d: Dims, params, tokens, labels, operand_dtype, block):
    h = jnp.take(params["embed"], tokens, axis=0)
    for c, bp in zip(d.pattern, params["blocks"]):
        if c == "M":
            mixer = lambda p, u: _mamba(d, p["mamba"], u, operand_dtype,
                                        block)
        elif c == "*":
            mixer = lambda p, u: _attention(d, p["attn"], u, operand_dtype)
        else:
            mixer = lambda p, u: _moe(d, p["moe"], u, operand_dtype)
        h = jax.checkpoint(
            lambda h, bp, mixer=mixer: h + mixer(bp, _rmsnorm(
                h, bp["norm"], d.eps)))(h, bp)
    h = _rmsnorm(h, params["norm_f"], d.eps).reshape(-1, d.d_model)
    labels = labels.reshape(-1)
    rows = TOKEN_BLOCK if h.shape[0] % TOKEN_BLOCK == 0 else h.shape[0]
    head = params["head"][:, :d.vocab]

    @jax.checkpoint
    def nll(total, i):
        hi = jax.lax.dynamic_slice_in_dim(h, i * rows, rows)
        li = jax.lax.dynamic_slice_in_dim(labels, i * rows, rows)
        logits = _matmul(hi, head, operand_dtype)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[:, None], axis=-1)[:, 0]
        return total + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(nll, jnp.zeros((), jnp.float32),
                            jnp.arange(h.shape[0] // rows))
    return total


@functools.lru_cache(maxsize=None)
def _block_grad(d: Dims, operand_dtype, block):
    return jax.jit(jax.value_and_grad(
        lambda p, t, l: _nll_sum(d, p, t, l, operand_dtype, block)))


def loss_and_grad(config: dict, params, tokens, labels, *,
                  operand_dtype=None):
    """Mean cross entropy over the batch and its gradient, in float32."""
    seq = tokens.shape[1]
    fn = _block_grad(dims(config), None if operand_dtype is None
                     else jnp.dtype(operand_dtype),
                     SCAN_BLOCK if seq % SCAN_BLOCK == 0 else seq)
    with jax.default_matmul_precision("highest"):
        total, grads = fn(params, tokens, labels)
    n = tokens.shape[0] * seq
    return total / n, jax.tree.map(lambda g: g / n, grads)
