"""moonlight-16b-a3b — [hf:moonshotai/Moonlight-16B-A3B; arXiv:2502.16982]

DeepSeek-V3 block (arXiv:2412.19437): 27L d_model=2048, vocab=163840
untied.  Latent attention (MLA): 16 heads, q straight from d to
16 x (128 + 64), keys and values from a 512-wide latent (RMSNorm'd) plus
one 64-wide RoPE key shared by every head; the cache holds the latent and
that key.  Layer 0 is dense (SwiGLU 11264); layers 1-26 are MoE: 64
routed experts of width 1408, 6 per token, sigmoid scores with a
per-expert correction bias for the choice (noaux_tc, one group), the
chosen scores normalised and scaled by 2.446, plus 2 shared experts.
"""

from .base import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab=163840,
    rope_theta=50_000.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_experts=64,
    top_k=6,
    d_expert=1408,
    router="sigmoid",
    routed_scale=2.446,
    n_shared_experts=2,
    first_k_dense=1,
    n_experts_held=64,
    norm_eps=1e-5,
    notes="every expert held here; a chip of an expert-parallel deployment"
          " sets n_experts_held and expert_lo to its share",
)
