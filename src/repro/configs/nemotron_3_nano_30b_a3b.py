"""NVIDIA-Nemotron-3-Nano-30B-A3B [hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16;
family paper Nemotron-H, arXiv:2504.03624].

52 blocks in the published ``hybrid_override_pattern``, each
``x + mixer(RMSNorm(x))`` (eps 1e-5): 23 Mamba-2 ("M": 64 heads x 64,
n_groups 8, state 128, conv 4 with bias, chunk 128, gated RMSNorm per group
of 512 channels), 23 MoE ("E": 128 routed relu^2 experts of width 1856,
top 6 on a sigmoid router with a selection-only bias, weights normalized
and x2.5, plus one shared expert of width 3712) and 6 attention ("*": GQA
32/2, head_dim 128, no rotary, no bias). d_model 2688, vocab 131072,
untied embeddings.
"""
from repro.config import ModelConfig

CONFIG = ModelConfig(
    name="nemotron-3-nano-30b-a3b",
    family="hybrid",
    layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    num_layers=52,
    d_model=2688,
    vocab_size=131072,
    num_heads=32,
    num_kv_heads=2,
    head_dim=128,
    rope_theta=10000.0,
    rope_pct=0.0,           # NoPE: the attention blocks carry no rotary
    mlp_variant="squared_relu",
    num_experts=128,
    router_experts=128,
    num_experts_per_tok=6,
    num_shared_experts=1,
    moe_d_ff=1856,
    shared_expert_d_ff=3712,
    moe_router="sigmoid",
    norm_topk_prob=True,
    routed_scaling=2.5,
    router_aux_loss_coef=0.0,
    ssm_state=128,
    ssm_heads=64,
    ssm_head_dim=64,
    ssm_n_groups=8,
    ssm_conv_width=4,
    ssm_chunk=128,
    ssm_norm_eps=1e-5,
    norm_eps=1e-5,
    tie_embeddings=False,
)
