"""Model FLOPs of a prefill of the latent-attention MoE decoder (the
DeepSeek-V3 block), worked out from its shapes.

Per prompt token, in every layer: the latent attention's projections
(``wq``, ``wkv_a``, ``wkv_b``, ``wo``) and causal attention (q.k over
nope + rope columns and p.v over v columns; each position attends to
itself and those before it: on average (prompt_len + 1) / 2 keys); in
the leading dense layers their SwiGLU; in the MoE layers the router over
every routed expert and the shared experts.  The routed experts count by
the rows the held experts computed (``expert_rows``: one token given to
one held expert in one layer), each a SwiGLU of ``moe_intermediate_size``.
The head runs at each prompt's last position only, as the prefill
computes it.  Norms, RoPE, softmax, the sort and the gathers are not
matrix products and are not counted.
"""

from __future__ import annotations

from typing import Any, Dict


def token_flops(spec: Dict[str, Any], prompt_len: int) -> float:
    """FLOPs of one prompt token, the routed experts left out."""
    d, h = spec["hidden_size"], spec["num_attention_heads"]
    nope, rope, vd = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                      spec["v_head_dim"])
    rank, layers = spec["kv_lora_rank"], spec["num_hidden_layers"]
    dense = spec["first_k_dense_replace"]
    fe, experts = spec["moe_intermediate_size"], spec["n_routed_experts"]
    proj = (d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + vd) + h * vd * d)
    attn = h * (nope + rope + vd) * (prompt_len + 1) / 2
    mlp = 3 * d * spec["intermediate_size"]
    moe = d * experts + 3 * d * spec["n_shared_experts"] * fe
    return 2.0 * (layers * (proj + attn) + dense * mlp
                  + (layers - dense) * moe)


def expert_row_flops(spec: Dict[str, Any]) -> float:
    """FLOPs of one row through one routed expert."""
    return 2.0 * 3 * spec["hidden_size"] * spec["moe_intermediate_size"]


def prefill_flops(spec: Dict[str, Any], prompts: int, prompt_len: int,
                  expert_rows: int) -> float:
    """FLOPs of prefilling ``prompts`` prompts of ``prompt_len`` tokens
    whose held experts computed ``expert_rows`` rows."""
    head = 2.0 * spec["hidden_size"] * spec["vocab_size"]
    return (prompts * (prompt_len * token_flops(spec, prompt_len) + head)
            + expert_rows * expert_row_flops(spec))
