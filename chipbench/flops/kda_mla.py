"""Model FLOPs of a prefill of the hybrid MoE decoder (Kimi Linear: KDA
layers beside latent attention without RoPE), worked out from its shapes.

Per prompt token: in every KDA layer its projections (``wqkv``, ``wb``,
``wo``), its two low-rank gates (``wf_a`` ``wf_b`` for the decay, ``wg_a``
``wg_b`` for the output) and the matrix products of the chunked form at
the program's chunk (``kda_chunk_size``: ``kda_scan_flops``); in every
latent-attention layer what ``flops/moe_mla.py`` counts there (its
projections and causal attention over nope + rope columns, on average
(prompt_len + 1) / 2 keys); in the leading dense layers their SwiGLU; in
the MoE layers the router over every routed expert and the shared
experts.  The routed experts count by the rows the held experts computed
(``expert_rows``), each a SwiGLU of ``moe_intermediate_size``.  The head
runs at each prompt's last position only.  The conv, norms, gates'
nonlinearities, decays, the sort and the gathers are not matrix products
and are not counted.

``kda_flops`` and ``kda_bytes`` count the chunked scan alone (what a
roofline share of the ``kda.chunk`` and ``kda.state`` scopes would read).
"""

from __future__ import annotations

from typing import Any, Dict, List


def _kinds(spec: Dict[str, Any]) -> List[str]:
    kda = spec["linear_attn_config"]["kda_layers"]
    return ["kda" if i + 1 in kda else "mla"
            for i in range(spec["num_hidden_layers"])]


def kda_scan_flops(spec: Dict[str, Any]) -> float:
    """FLOPs of the chunked form for one token in one KDA layer.  Per
    chunk of C and per head (K key, V value channels): the decayed
    q.k and k.k products of the lower triangle, C^2 K each; the
    triangular solve for [U | W], C^2 (K + V) / 2 multiply-adds; W S,
    (q exp G) S and the state's update, C K V each; M U, C^2 V / 2."""
    lin = spec["linear_attn_config"]
    c, h = spec["kda_chunk_size"], lin["num_heads"]
    k = v = lin["head_dim"]
    per_chunk = 2.0 * (c * c * k + c * c * (k + v) / 2 + 3 * c * k * v
                       + c * c * v / 2)
    return h * per_chunk / c


def token_flops(spec: Dict[str, Any], prompt_len: int) -> float:
    """FLOPs of one prompt token, the routed experts left out."""
    d, h = spec["hidden_size"], spec["num_attention_heads"]
    nope, rope, vd = (spec["qk_nope_head_dim"], spec["qk_rope_head_dim"],
                      spec["v_head_dim"])
    rank = spec["kv_lora_rank"]
    lin = spec["linear_attn_config"]
    hk, r = lin["num_heads"] * lin["head_dim"], spec["kda_gate_rank"]
    kinds = _kinds(spec)
    layers, dense = len(kinds), spec["first_k_dense_replace"]
    fe, experts = spec["moe_intermediate_size"], spec["num_experts"]
    kda_proj = (d * 3 * hk + d * lin["num_heads"] + hk * d
                + 2 * (d * r + r * hk))
    mla_proj = (d * h * (nope + rope) + d * (rank + rope)
                + rank * h * (nope + vd) + h * vd * d)
    attn = h * (nope + rope + vd) * (prompt_len + 1) / 2
    mlp = 3 * d * spec["intermediate_size"]
    moe = d * experts + 3 * d * spec["num_shared_experts"] * fe
    n_kda = kinds.count("kda")
    return (2.0 * (n_kda * kda_proj + (layers - n_kda) * (mla_proj + attn)
                   + dense * mlp + (layers - dense) * moe)
            + n_kda * kda_scan_flops(spec))


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


def kda_flops(spec: Dict[str, Any], prompts: int, prompt_len: int) -> float:
    """FLOPs of the chunked scan over a prefill, every KDA layer."""
    return (_kinds(spec).count("kda") * prompts * prompt_len
            * kda_scan_flops(spec))


def kda_bytes(spec: Dict[str, Any], prompts: int, prompt_len: int) -> float:
    """Bytes the chunked scan must move over a prefill, every KDA layer:
    q, k, v and the decays read and the output written, float32 [T, H K],
    b read [T, H], and the state written [H, K, V] a prompt."""
    lin = spec["linear_attn_config"]
    h, k = lin["num_heads"], lin["head_dim"]
    per_prompt = 4.0 * (prompt_len * (5 * h * k + h) + h * k * k)
    return _kinds(spec).count("kda") * prompts * per_prompt
