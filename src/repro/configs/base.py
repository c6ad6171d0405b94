"""Architecture configuration: every assigned architecture is a frozen
``ArchConfig``, with its parameter count and a ``reduced()`` width for CPU
tests.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import List, Optional, Tuple

__all__ = ["ArchConfig"]


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    # attention details
    head_dim: Optional[int] = None          # default d_model // n_heads
    qkv_bias: bool = False                  # qwen1.5 family
    qk_norm: bool = False                   # chameleon
    rope_theta: float = 10_000.0
    swa_window: int = 0                     # 0 => full attention
    # latent attention (MLA, DeepSeek-V2/V3), on when kv_lora_rank > 0: the
    # cache holds a kv_lora_rank latent plus one qk_rope_head_dim RoPE key
    # shared by every head
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    mla_nope: bool = False                  # no RoPE: the rope columns stay
    #                                         in every shape, unrotated
    # Kimi Delta Attention (KDA, Kimi Linear), on when kda_layers is set: the
    # 1-based numbers of the published layers that are KDA (gated delta-rule
    # linear attention, kda_heads x kda_head_dim, a causal conv of kda_conv
    # over time, decay and output gates of rank kda_gate_rank); every other
    # layer is latent attention.  Prefill runs the chunked form, kda_chunk
    # tokens a chunk
    kda_layers: Tuple[int, ...] = ()
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv: int = 0
    kda_gate_rank: int = 0
    kda_chunk: int = 64

    # MoE
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0                       # expert FFN hidden (arctic: 4864)
    dense_residual: bool = False            # arctic: dense MLP in parallel
    capacity_factor: float = 1.25
    router: str = "softmax"                 # "sigmoid": DeepSeek-V3 scores,
    #                                         top-k of score + correction bias
    routed_scale: float = 1.0               # on the normalised top-k weights
    n_shared_experts: int = 0               # always on: one SwiGLU n*d_expert
    first_k_dense: int = 0                  # leading dense layers (d_ff)
    # > 0: the expert-share layer, holding experts [expert_lo, expert_lo +
    # n_experts_held) of n_experts, routing over all and dropping no token
    n_experts_held: int = 0
    expert_lo: int = 0

    # SSM / hybrid
    ssm_state: int = 0                      # mamba2 state size N
    ssm_head_dim: int = 64                  # rwkv/mamba head size
    attn_every: int = 0                     # zamba2: shared attn block period
    ssm_expand: int = 2                     # mamba2 expansion factor

    # training / numerics
    tie_embeddings: bool = False
    optimizer_moment_dtype: str = "float32"  # "bfloat16" for the huge MoEs
    use_master_weights: bool = True
    lr_schedule: str = "cosine"             # "wsd" for minicpm
    depth_scaled_residual: bool = False     # minicpm (µP-ish)
    norm_eps: float = 1e-6                  # RMSNorm epsilon

    # serving
    kv_cache_dtype: str = "bfloat16"        # "int8" where HBM requires it

    notes: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    def layer_kinds(self) -> List[str]:
        """Each layer's attention in order: "kda" or "attn"."""
        return ["kda" if i + 1 in self.kda_layers else "attn"
                for i in range(self.n_layers)]

    # ---- parameter counting ------------------------------------------------
    def _attn_params(self, kind: str = "attn") -> int:
        d, H = self.d_model, self.n_heads
        if kind == "kda":
            hk, r = self.kda_heads * self.kda_head_dim, self.kda_gate_rank
            return (d * 3 * hk + self.kda_conv * 3 * hk   # wqkv, conv
                    + d * self.kda_heads + self.kda_heads  # wb, A_log
                    + d * r + r * hk + hk                 # wf_a, wf_b, dt_bias
                    + d * r + r * hk + hk                 # wg_a, wg_b, g_bias
                    + self.kda_head_dim + hk * d)         # o_norm, wo
        if self.kv_lora_rank:
            r, rope = self.kv_lora_rank, self.qk_rope_head_dim
            return (d * H * (self.qk_nope_head_dim + rope)   # wq
                    + d * (r + rope) + r                     # wkv_a, kv_norm
                    + r * H * (self.qk_nope_head_dim + self.v_head_dim)
                    + H * self.v_head_dim * d)               # wo
        hd, KV = self.hd, self.n_kv_heads
        return d * (H * hd) + 2 * d * (KV * hd) + (H * hd) * d

    def _moe_layer_params(self, experts: int) -> int:
        """One MoE layer's FFN side: ``experts`` experts, router (and its
        correction bias), shared experts, dense residual."""
        d, E = self.d_model, self.n_experts
        fe = self.d_expert or self.d_ff
        n = (experts + self.n_shared_experts) * 3 * d * fe + d * E
        if self.router == "sigmoid":
            n += E
        return n + (3 * d * self.d_ff if self.dense_residual else 0)

    def param_count(self) -> int:
        d, f, L, V = self.d_model, self.d_ff, self.n_layers, self.vocab
        emb = V * d * (1 if self.tie_embeddings else 2)
        if self.family == "ssm":            # rwkv6
            # tmix: r,k,v,g,o (d*d each) + decay/lora small; cmix: 2 mats
            per_layer = 5 * d * d + 2 * d * int(3.5 * d)
            return emb + L * per_layer
        attn = self._attn_params()
        mlp_dense = 3 * d * f               # SwiGLU: w1, w3, w2
        if self.family == "hybrid":
            din = self.ssm_expand * d
            mamba = (d * 2 * din              # in_proj (x, z)
                     + din * (2 * self.ssm_state)   # B, C projections
                     + din + din * d)               # dt + out_proj
            # the shared block is ONE set of weights reused at every call site
            shared = attn + mlp_dense
            return emb + L * mamba + shared
        norms = 2 * d                       # ln1, ln2
        if self.family == "moe":
            held = self.n_experts_held or self.n_experts
            k = self.first_k_dense
            attn_all = sum(self._attn_params(kind)
                           for kind in self.layer_kinds())
            return (emb + d + attn_all + L * norms + k * mlp_dense
                    + (L - k) * self._moe_layer_params(held))
        return emb + d + L * (attn + mlp_dense + norms)

    # ---- reduced config for CPU smoke tests --------------------------------
    def reduced(self) -> "ArchConfig":
        experts = min(self.n_experts, max(4, self.top_k + 2))
        mla = self.kv_lora_rank > 0
        return replace(
            self,
            n_layers=min(self.n_layers,
                         4 if self.attn_every or self.kda_layers else 2),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(4, max(1, self.n_kv_heads * 4 // self.n_heads)),
            head_dim=32,
            d_ff=256,
            vocab=512,
            n_experts=experts,
            n_experts_held=min(self.n_experts_held, experts),
            kv_lora_rank=64 if mla else 0,
            qk_nope_head_dim=32 if mla else 0,
            qk_rope_head_dim=16 if mla else 0,
            v_head_dim=32 if mla else 0,
            kda_heads=4 if self.kda_layers else 0,
            kda_head_dim=32 if self.kda_layers else 0,
            kda_gate_rank=32 if self.kda_layers else 0,
            kda_chunk=16 if self.kda_layers else self.kda_chunk,
            d_expert=64 if self.n_experts else 0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=32,
            attn_every=2 if self.attn_every else 0,
            swa_window=min(self.swa_window, 64) if self.swa_window else 0,
        )

