"""Model FLOPs of the dense decoder, worked out from its shapes.

Per token of a training step: the forward pass's matrix products (the
layers' projections and the tied head over ``vocab_size``) and causal
attention (each position attends to itself and those before it: on
average (seq + 1) / 2 keys), times 3 for forward and backward.
Recomputation under remat is not counted, nor are the embedding lookup,
norms, softmax and the optimizer, which are not matrix products.
"""

from __future__ import annotations

from typing import Any, Dict


def forward_flops_per_token(spec: Dict[str, Any], seq: int) -> float:
    d, f = spec["hidden_size"], spec["intermediate_size"]
    h, kv, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                 spec["head_dim"])
    layers, vocab = spec["num_hidden_layers"], spec["vocab_size"]
    proj = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    attn = 2 * h * hd * (seq + 1) / 2          # q.k and p.v, causal
    return 2.0 * layers * proj + 2.0 * layers * attn + 2.0 * d * vocab


def train_flops_per_token(spec: Dict[str, Any], seq: int) -> float:
    return 3.0 * forward_flops_per_token(spec, seq)
