"""Plain float32 reference of the mamba2-130m language model and its loss.

Written from the Mamba-2 paper (Dao & Gu, "Transformers are SSMs",
arXiv:2405.21060, section 7 and its listing) in straightforward
``jax.numpy``, with the state recurrence stepped one position at a time:

    h_t = exp(dt_t * A) h_{t-1} + dt_t x_t B_t^T      (per head, A scalar)
    y_t = h_t C_t + D x_t

Each layer is ``u + out_proj(SSM(conv(in_proj(rmsnorm(u)))) * silu(z))``,
the embedding is tied to the output head, and the loss is the mean
next-token cross entropy. It imports nothing of the system under test.

Departures from the published block, each one made because the system
under test computes it so and the reference has to compute the same
function:

* no gated RMSNorm between ``y * silu(z)`` and ``out_proj`` (the paper's
  block normalizes there);
* the embedding table has ``ceil(vocab / 256) * 256`` rows, of which the
  head uses the first ``vocab_size``;
* no ``dt`` clamp (the published code has none by default either).

The residual stream is float32 throughout (the published code keeps it
in float32 as well). Matrix products run at ``Precision.HIGHEST``. A
``operand_dtype`` rounds the operands of every projection and of the
head to a lower precision first; that is the control of the comparison
(float8 where the configuration computes in bfloat16).

The backward pass fits one chip at the timed size because every layer is
rematerialized and the scan over positions keeps its state only every
``SCAN_BLOCK`` positions: for a TPU v5e the compiler counts 0.52 GB of
arguments, 5.82 GB of temporaries and 0.52 GB of outputs at batch 8 x
seq 2048.
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


class Dims(NamedTuple):
    layers: int
    d_model: int
    d_inner: int
    heads: int
    head_dim: int
    state: int
    conv: int
    vocab: int
    rows: int      # embedding rows (vocab padded to a multiple of 256)
    eps: float


def dims(config: dict) -> Dims:
    m = config["model"]
    d_inner = m["ssm_expand"] * m["d_model"]
    vocab = m["vocab_size"]
    return Dims(layers=m["n_layers"], d_model=m["d_model"], d_inner=d_inner,
                heads=d_inner // m["ssm_head_dim"],
                head_dim=m["ssm_head_dim"], state=m["ssm_state"],
                conv=m["ssm_conv_width"], vocab=vocab,
                rows=-(-vocab // 256) * 256, eps=m["norm_eps"])


def init_params(config: dict, key) -> dict:
    """Weights drawn from ``key`` as the published code initializes them:
    PyTorch's default uniform for the projections (``out_proj`` scaled by
    1/sqrt(layers)), ``A ~ U[1, 16]``, ``dt ~ logU[1e-3, 1e-1]`` stored as
    its inverse softplus, ``D = 1``, unit norms, N(0, 0.02) embeddings.
    The tree has the layout the trainer's parameters have."""
    d = dims(config)
    L, D, E, H, N, W = (d.layers, d.d_model, d.d_inner, d.heads, d.state,
                        d.conv)
    proj = 2 * E + 2 * N + H
    conv_ch = E + 2 * N
    ks = jax.random.split(key, 7)

    def uniform(k, shape, bound):
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)

    dt = jnp.exp(jax.random.uniform(ks[5], (L, H), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    mamba = {
        "in_proj": uniform(ks[1], (L, D, proj), 1.0 / math.sqrt(D)),
        "conv_w": uniform(ks[2], (L, W, conv_ch), 1.0 / math.sqrt(W)),
        "conv_b": uniform(ks[3], (L, conv_ch), 1.0 / math.sqrt(W)),
        "A_log": jnp.log(jax.random.uniform(ks[4], (L, H), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "D_skip": jnp.ones((L, H), jnp.float32),
        "out_proj": uniform(ks[6], (L, E, D), 1.0 / math.sqrt(E))
        / math.sqrt(L),
    }
    return {
        "embed": 0.02 * jax.random.normal(ks[0], (d.rows, D), jnp.float32),
        "layers": {"mamba": mamba, "norm": jnp.ones((L, D), jnp.float32)},
        "norm_f": jnp.ones((D,), jnp.float32),
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
    B, C: (b, S, N) -> y: (b, S, H, P) without the D skip."""
    b, S, H, P = x.shape
    N = B.shape[-1]

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = (h * jnp.exp(dtt * A)[:, :, None, None]
             + (dtt[:, :, None] * xt)[..., None] * bt[:, None, None, :])
        return h, jnp.einsum("bhpn,bn->bhp", h, ct, precision=HIGHEST)

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
    b, S, _ = u.shape
    E, N, H, P = d.d_inner, d.state, d.heads, d.head_dim
    proj = _matmul(u, p["in_proj"], operand_dtype)
    z, xbc, dt = proj[..., :E], proj[..., E:2 * E + 2 * N], proj[..., 2 * E + 2 * N:]
    W = p["conv_w"].shape[0]
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = p["conv_b"] + sum(pad[:, k:k + S, :] * p["conv_w"][k]
                             for k in range(W))
    xbc = jax.nn.silu(conv)
    x = xbc[..., :E].reshape(b, S, H, P)
    B, C = xbc[..., E:E + N], xbc[..., E + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = _ssm(x, dt, A, B, C, block) + x * p["D_skip"][:, None]
    y = y.reshape(b, S, E) * jax.nn.silu(z)
    return _matmul(y, p["out_proj"], operand_dtype)


def _nll_sum(d: Dims, params, tokens, labels, operand_dtype, block):
    h = jnp.take(params["embed"], tokens, axis=0)

    @jax.checkpoint
    def layer(h, lp):
        return h + _mamba(d, lp["mamba"], _rmsnorm(h, lp["norm"], d.eps),
                          operand_dtype, block), None

    h, _ = jax.lax.scan(layer, h, params["layers"])
    h = _rmsnorm(h, params["norm_f"], d.eps)
    logits = _matmul(h, params["embed"][:d.vocab].T, operand_dtype)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


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
