"""Operations one training token of nemotron3-nano-30b-a3b requires, and
the work of its attention kernel.

Forward multiply-adds per token, counted from the configuration's shapes,
block by block of the layer pattern:

* M, Mamba-2: ``in_proj`` (d_model x (E + E + 2 G N + H)), ``out_proj``
  (E x d_model), the depthwise causal convolution (width x (E + 2 G N)),
  and the SSD chunked scan at chunk length Q: the C B^T scores of each of
  the G groups (Q G N), the masked mix into the outputs (Q H P), the
  chunk states (H P N) and the states read back into the outputs
  (H P N). The Q x Q products are counted whole, masked half included,
  as the chunked algorithm states them;
* ``*``, attention: the q, k, v and o projections, and q k^T and the
  probabilities times v over the whole sequence (2 S H hd), the masked
  half included, as the SSD is counted;
* E, MoE: the router (d_model x experts); the held experts at their
  expected rows, k x held / experts of a token, each 2 d_model x ffn;
  the shared expert, 2 d_model x shared ffn;
* the head: d_model x vocab (the input embedding is a gather).

A multiply-add is two operations and the backward pass twice the
forward, so a token costs 6 x the forward multiply-adds. Operations
rematerialized to save memory are not counted.

:func:`attention_kernel_work` gives the splash attention kernel's own
operations and HBM bytes in one training step, counting only the causal
half of the S x S products that it computes.
"""

from __future__ import annotations


def _mamba_macs(m: dict, seq: int) -> float:
    D, N, P = m["d_model"], m["ssm_state"], m["ssm_head_dim"]
    G, H = m["ssm_groups"], m["ssm_heads"]
    E = H * P
    Q = min(m["ssm_chunk"], seq)
    conv_ch = E + 2 * G * N
    return (D * (E + conv_ch + H) + E * D + m["ssm_conv_width"] * conv_ch
            + Q * G * N + Q * H * P + 2 * H * P * N)


def _attention_macs(m: dict, seq: int) -> float:
    D, H, KV, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return D * (H + 2 * KV) * hd + H * hd * D + 2 * seq * H * hd


def _moe_macs(m: dict) -> float:
    D = m["d_model"]
    held = m["experts_held"] or m["n_experts"]
    rows = m["experts_per_token"] * held / m["n_experts"]
    return (D * m["n_experts"] + rows * 2 * D * m["moe_d_ff"]
            + 2 * D * m["shared_d_ff"])


def train_flops_per_token(config: dict) -> float:
    m, seq = config["model"], config["shape"]["seq"]
    per = {"M": _mamba_macs(m, seq), "*": _attention_macs(m, seq),
           "E": _moe_macs(m)}
    macs = sum(per[c] for c in m["layer_pattern"]) + m["d_model"] * m[
        "vocab_size"]
    return 6.0 * macs


def attention_kernel_work(config: dict) -> dict:
    """``{"flops", "bytes"}`` of the splash kernels one training step
    runs, over every attention block. Per block: the forward kernel twice
    (in the forward pass, and again in the backward pass: the block is
    rematerialized and no policy saves a kernel's output; once with remat
    "none"), each two causal-half products (q k^T, p v); the dq kernel
    three (q k^T, dO v^T, dS k) and the dkv kernel four (q k^T, dO v^T,
    p^T dO, dS^T q). Bytes: each kernel reads its bf16 operands once and
    writes its results once, with a float32 row statistic per query row
    (log-sum-exp, and the dO . O row sums for the backward kernels)."""
    m, shape = config["model"], config["shape"]
    B, S = shape["batch"], shape["seq"]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    n_attn = m["layer_pattern"].count("*")
    fwd_calls = 1 if config["train"].get("remat", "none") == "none" else 2
    # one causal-half S x S product over every query head, in operations
    product = 2.0 * B * H * hd * S * (S + 1) / 2
    q = 2.0 * B * S * H * hd             # bf16 bytes of q, o, dO or dq
    kv = 2.0 * B * S * KV * hd           # bf16 bytes of k, v, dk or dv
    row = 4.0 * B * S * H                # one f32 statistic per query row
    fwd = (2 * product, q + 2 * kv + q + row)
    dq = (3 * product, q + 2 * kv + q + 2 * row + q)
    dkv = (4 * product, q + 2 * kv + q + 2 * row + 2 * kv)
    flops = n_attn * (fwd_calls * fwd[0] + dq[0] + dkv[0])
    nbytes = n_attn * (fwd_calls * fwd[1] + dq[1] + dkv[1])
    return {"flops": flops, "bytes": nbytes}
