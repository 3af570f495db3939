"""nemotron3-nano-30b-a3b — NVIDIA Nemotron 3 Nano 30B-A3B (``nemotron_h``):
52 pre-norm residual blocks after ``hybrid_override_pattern``, 23 Mamba-2
(64 heads x 64, 8 B/C groups, state 128, gated group-wise RMSNorm), 23
MoE (128 routed relu^2 experts of width 1856, 6 per token, sigmoid router
with a score-correction bias, routed scaling 2.5, one shared expert of
width 3712) and 6 GQA attention blocks (32 query heads, 2 KV heads, head
dim 128, no rotation); untied 131,072-row vocabulary.
[hf: nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json]"""

from .base import ModelConfig

CONFIG = ModelConfig(
    name="nemotron3-nano-30b-a3b", family="nemotron_h",
    n_layers=52, d_model=2688, n_heads=32, n_kv_heads=2, head_dim=128,
    d_ff=0, vocab_size=131_072,
    layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    n_experts=128, experts_per_token=6, n_shared_experts=1,
    moe_d_ff=1856, shared_d_ff=3712, routed_scaling=2.5,
    ssm_state=128, ssm_head_dim=64, ssm_heads=64, ssm_groups=8,
    ssm_conv_width=4, ssm_chunk=128, ssm_gated_norm=True,
    use_rope=False, norm_eps=1e-5,
)
