"""kimi-linear-48b-a3b — [hf:moonshotai/Kimi-Linear-48B-A3B-Instruct;
arXiv:2510.26692]

27L d_model=2304, vocab=163840 untied.  Two kinds of attention in a
published order, 3 KDA : 1 MLA: layers 1-3, 5-7, ..., 25-26 are Kimi
Delta Attention (gated delta-rule linear attention: 32 heads x 128, a
causal conv of 4 over q, k and v, per-channel decay, delta-rule state
of 128 x 128 per head); layers 4, 8, ..., 24 and 27 are latent attention
without RoPE (32 heads, nope 128, rope 64 left unrotated, v 128, latent
512, no query latent).  Layer 1 is dense (SwiGLU 9216); layers 2-27 are
MoE: 256 routed experts of width 1024, 8 per token, sigmoid scores with
a correction bias for the choice, the chosen scores normalised and
scaled by 2.446, plus 1 shared expert.  Not given by config.json and
taken here: the decay and output gates of rank 128 (the head size), an
output-gate bias, no conv bias.
"""

from .base import ArchConfig

CONFIG = ArchConfig(
    name="kimi-linear-48b-a3b",
    family="moe",
    n_layers=27,
    d_model=2304,
    n_heads=32,
    n_kv_heads=32,
    d_ff=9216,
    vocab=163840,
    head_dim=72,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    mla_nope=True,
    kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21,
                22, 23, 25, 26),
    kda_heads=32,
    kda_head_dim=128,
    kda_conv=4,
    kda_gate_rank=128,
    n_experts=256,
    top_k=8,
    d_expert=1024,
    router="sigmoid",
    routed_scale=2.446,
    n_shared_experts=1,
    first_k_dense=1,
    n_experts_held=256,
    norm_eps=1e-5,
    notes="every expert held here; a chip of an expert-parallel deployment"
          " sets n_experts_held and expert_lo to its share",
)
