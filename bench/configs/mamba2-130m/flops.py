"""Operations one training token of mamba2-130m requires.

Forward multiply-adds per token, counted from the configuration's shapes:

* per layer: ``in_proj`` (d_model x (2 E + 2 N + H)), ``out_proj``
  (E x d_model), the depthwise causal convolution (width x (E + 2 N)),
  and the SSD chunked scan at chunk length Q: the C B^T scores (Q N),
  the masked mix into the outputs (Q H P), the chunk states (H P N) and
  the states read back into the outputs (H P N). The Q x Q products are
  counted whole, masked half included, as the chunked algorithm states
  them (the usual convention for attention);
* the tied head: d_model x vocab (the input embedding is a gather, and
  the rows that only pad the table are not counted).

A multiply-add is two operations and the backward pass twice the
forward, so a token costs 6 x the forward multiply-adds. Operations
rematerialized to save memory are not counted.
"""

from __future__ import annotations


def train_flops_per_token(config: dict) -> float:
    m = config["model"]
    D, N, P = m["d_model"], m["ssm_state"], m["ssm_head_dim"]
    E = m["ssm_expand"] * D
    H = E // P
    Q = min(m["ssm_chunk"], config["shape"]["seq"])
    per_layer = (D * (2 * E + 2 * N + H) + E * D
                 + m["ssm_conv_width"] * (E + 2 * N)
                 + Q * N + Q * H * P + 2 * H * P * N)
    macs = m["n_layers"] * per_layer + D * m["vocab_size"]
    return 6.0 * macs
