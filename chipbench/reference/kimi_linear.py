"""Plain reference of Kimi-Linear-48B-A3B's block, one chip's share of it.

Straight ``jax.numpy`` from the configuration file's sizes; it imports
nothing of the program (the MoE half, norms and head are
``reference/moonlight.py``'s, the same lab's DeepSeek-V3 MoE).  Kimi
Linear (arXiv:2510.26692) as its config.json sets it:

  * RMSNorm (eps ``rms_norm_eps``) before attention and before the FFN,
    residual adds, a final RMSNorm and an untied head;
  * the layers of ``linear_attn_config.kda_layers`` (1-based) are Kimi
    Delta Attention, on the normed x:
      q, k, v = SiLU(conv(x W_qkv)), a depthwise causal conv of
      ``short_conv_kernel_size`` over time, ``num_heads`` x ``head_dim``
      each; q, k L2-normalised per head, q scaled by head_dim^-1/2;
      b = sigmoid(x W_b); g = -exp(A_log) softplus(x W_fa W_fb + dt_bias);
      per head, token by token, S = Diag(exp g) S, then
      S += b k (v - S^T k)^T, o = S^T q;
      out = W_o [RMSNorm_head(o) sigmoid(x W_ga W_gb + g_bias)];
  * the others (``full_attn_layers``) are latent attention without RoPE
    (``mla_use_nope``): q = x Wq per head [nope | rope]; [c | k_rope] =
    x Wkv_a, c RMSNorm'd; per head [k_nope | v] = c Wkv_b; scores
    q_nope.k_nope + q_rope.k_rope over sqrt(nope + rope), nothing
    rotated, causal softmax, o Wo;
  * layers before ``first_k_dense_replace``: a SwiGLU of
    ``intermediate_size``; the others: sigmoid scores over all
    ``num_experts``, the top ``num_experts_per_token`` of score +
    correction bias, the chosen scores renormalised and times
    ``routed_scaling_factor``, each chosen expert a SwiGLU of
    ``moe_intermediate_size``, plus ``num_shared_experts`` shared.

The sizes config.json leaves open are the file's ``assumed``.  Departures
(the file's ``departures``): only the held experts ``[lo, lo +
num_experts_held)`` add their part, the others lie on other chips;
random weights from the seed, the correction bias among them.

Everything runs in ``dtype``: float32 at "highest" matmul precision is the
reference, a lower one the control.  The whole causal forward runs with
no cache, one layer at a time: KDA as the per-token recurrence (not a
chunked form), attention over blocks of query rows, each held expert
densely over every token, weighted by its gate.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference.moonlight import (NEG, _cast, _dense, _norm,
                                           _swiglu_weights, logits, moe,
                                           rms_norm, swiglu)


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    nope: int
    rope: int
    vd: int
    rank: int
    ff: int
    fe: int
    experts: int
    held: int
    lo: int
    top_k: int
    shared: int
    dense_layers: int
    layers: int
    kinds: Tuple[str, ...]       # "kda" or "mla", each layer in order
    kda_heads: int
    kda_dim: int
    conv: int
    gate_rank: int
    vocab: int
    vocab_rows: int
    eps: float
    routed_scale: float
    bias_scale: float

    @classmethod
    def of(cls, spec: Dict[str, Any]) -> "Dims":
        if (spec["moe_router_activation_func"], spec["moe_renormalize"],
                spec["num_expert_group"], spec["topk_group"],
                spec["q_lora_rank"], spec["mla_use_nope"]) != (
                    "sigmoid", True, 1, 1, None, True):
            raise ValueError("not the block this reference computes")
        lin = spec["linear_attn_config"]
        pad = spec["vocab_padded_to"]
        lo, hi = spec["deployment"]["held_experts"]
        if hi - lo != spec["num_experts_held"]:
            raise ValueError("held_experts does not match num_experts_held")
        layers = spec["num_hidden_layers"]
        kinds = tuple("kda" if i + 1 in lin["kda_layers"] else "mla"
                      for i in range(layers))
        return cls(d=spec["hidden_size"], heads=spec["num_attention_heads"],
                   nope=spec["qk_nope_head_dim"],
                   rope=spec["qk_rope_head_dim"], vd=spec["v_head_dim"],
                   rank=spec["kv_lora_rank"], ff=spec["intermediate_size"],
                   fe=spec["moe_intermediate_size"],
                   experts=spec["num_experts"],
                   held=spec["num_experts_held"], lo=lo,
                   top_k=spec["num_experts_per_token"],
                   shared=spec["num_shared_experts"],
                   dense_layers=spec["first_k_dense_replace"],
                   layers=layers, kinds=kinds, kda_heads=lin["num_heads"],
                   kda_dim=lin["head_dim"],
                   conv=lin["short_conv_kernel_size"],
                   gate_rank=spec["kda_gate_rank"],
                   vocab=spec["vocab_size"],
                   vocab_rows=(spec["vocab_size"] + pad - 1) // pad * pad,
                   eps=spec["rms_norm_eps"],
                   routed_scale=spec["routed_scaling_factor"],
                   bias_scale=spec["e_score_correction_bias_scale"])

    def stacks(self) -> Dict[str, Tuple[int, ...]]:
        """The program's stacks, each the layers it holds, in order: the
        leading dense layers (KDA), then the MoE layers of each kind."""
        moe = range(self.dense_layers, self.layers)
        return {"dense": tuple(range(self.dense_layers)),
                "kda": tuple(i for i in moe if self.kinds[i] == "kda"),
                "mla": tuple(i for i in moe if self.kinds[i] == "mla")}


# ----------------------------------------------------------------- weights

def _kda_weights(dm: Dims, key) -> Dict[str, Any]:
    k = jax.random.split(key, 11)
    hk, r = dm.kda_heads * dm.kda_dim, dm.gate_rank
    dt = jnp.exp(jax.random.uniform(k[5], (hk,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {"wqkv": _dense(k[0], dm.d, 3 * hk),
            "conv": jax.random.normal(k[1], (dm.conv, 3 * hk), jnp.float32)
            * dm.conv ** -0.5,
            "wb": _dense(k[2], dm.d, dm.kda_heads),
            "wf_a": _dense(k[3], dm.d, r), "wf_b": _dense(k[4], r, hk),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(k[6], (dm.kda_heads,),
                                                jnp.float32, 1.0, 16.0)),
            "wg_a": _dense(k[7], dm.d, r), "wg_b": _dense(k[8], r, hk),
            "g_bias": 0.1 * jax.random.normal(k[9], (hk,), jnp.float32),
            "o_norm": _norm(k[10], dm.kda_dim),
            "wo": _dense(jax.random.fold_in(key, 11), hk, dm.d)}


def _mla_weights(dm: Dims, key) -> Dict[str, Any]:
    k = jax.random.split(key, 4)
    h = dm.heads
    return {"wq": _dense(k[0], dm.d, h * (dm.nope + dm.rope)),
            "wkv_a": _dense(k[1], dm.d, dm.rank + dm.rope),
            "kv_norm": _norm(k[2], dm.rank),
            "wkv_b": _dense(k[3], dm.rank, h * (dm.nope + dm.vd)),
            "wo": _dense(jax.random.fold_in(key, 4), h * dm.vd, dm.d)}


def _layer(dm: Dims, key, kind: str, moe_layer: bool) -> Dict[str, Any]:
    k = jax.random.split(key, 6)
    attn = (_kda_weights if kind == "kda" else _mla_weights)(dm, k[0])
    out = {"ln1": _norm(k[1], dm.d), "attn": attn, "ln2": _norm(k[2], dm.d)}
    if not moe_layer:
        out["mlp"] = _swiglu_weights(k[3], dm.d, dm.ff)
        return out
    m = _swiglu_weights(k[3], dm.d, dm.fe, (dm.held,))
    m["router"] = _dense(k[4], dm.d, dm.experts)
    m["router_bias"] = dm.bias_scale * jax.random.normal(
        k[5], (dm.experts,), jnp.float32)
    m["shared"] = _swiglu_weights(jax.random.fold_in(k[3], 1), dm.d,
                                  dm.shared * dm.fe)
    out["moe"] = m
    return out


def serve_weights(dm: Dims, seed: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The share's serving weights from the seed, in the program's tree
    (``emb``; ``dense``, ``kda`` and ``mla``, the layers of each stack
    stacked), in the type they are served in."""
    k_emb, k_layers = jax.random.split(jax.random.PRNGKey(seed))
    ke = jax.random.split(k_emb, 3)
    keys = jax.random.split(k_layers, dm.layers)
    w = {"emb": {"tok": jax.random.normal(ke[0], (dm.vocab_rows, dm.d),
                                          jnp.float32) * 0.02,
                 "ln_f": _norm(ke[1], dm.d),
                 "out": _dense(ke[2], dm.d, dm.vocab_rows)}}
    for name, idx in dm.stacks().items():
        if idx:
            kind, moe_layer = ("kda", False) if name == "dense" else (
                name, True)
            w[name] = jax.vmap(lambda k: _layer(dm, k, kind, moe_layer))(
                keys[jnp.asarray(idx)])
    return jax.tree.map(lambda x: x.astype(dtype), w)


# ----------------------------------------------------------------- model

def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def kda_recurrence(q, k, v, g, b):
    """The gated delta rule token by token from a zero state: q, k, g
    [B, T, H, K], v [B, T, H, V], b [B, T, H] -> o [B, T, H, V]."""
    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None] * S
        err = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S)
        S = S + jnp.einsum("bhk,bhv->bhkv", b_t[..., None] * k_t, err)
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S)

    bsz, _, h, dk = q.shape
    S = jnp.zeros((bsz, h, dk, v.shape[-1]), q.dtype)
    _, o = lax.scan(step, S, tuple(jnp.moveaxis(a, 1, 0)
                                   for a in (q, k, v, g, b)))
    return jnp.moveaxis(o, 0, 1)


def kda(dm: Dims, a, x):
    """Kimi Delta Attention over x [B, T, d] (normed), from a zero state."""
    b, t, _ = x.shape
    h, kd = dm.kda_heads, dm.kda_dim
    z = jnp.pad(x @ a["wqkv"], ((0, 0), (dm.conv - 1, 0), (0, 0)))
    y = sum(a["conv"][i] * z[:, i:i + t] for i in range(dm.conv))
    y = jax.nn.silu(y).reshape(b, t, 3, h, kd)
    q, k, v = _l2(y[:, :, 0]) * kd ** -0.5, _l2(y[:, :, 1]), y[:, :, 2]
    beta = jax.nn.sigmoid(x @ a["wb"])
    f = (x @ a["wf_a"] @ a["wf_b"] + a["dt_bias"]).reshape(b, t, h, kd)
    g = -jnp.exp(a["A_log"])[:, None] * jax.nn.softplus(f)
    gate = jax.nn.sigmoid(x @ a["wg_a"] @ a["wg_b"] + a["g_bias"])
    o = kda_recurrence(q, k, v, g, beta)
    o = rms_norm(o, a["o_norm"], dm.eps).reshape(b, t, h * kd) * gate
    return o @ a["wo"]


def attention(dm: Dims, a, x, block: int):
    """Latent attention without RoPE over x [B, T, d] (normed), causal, in
    blocks of ``block`` query rows."""
    b, t, _ = x.shape
    h, r = dm.heads, dm.rank
    q = (x @ a["wq"]).reshape(b, t, h, dm.nope + dm.rope)
    ckv = x @ a["wkv_a"]
    c = rms_norm(ckv[..., :r], a["kv_norm"], dm.eps)
    k_rope = ckv[..., r:]                                      # [B, T, rope]
    kv = (c @ a["wkv_b"]).reshape(b, t, h, dm.nope + dm.vd)
    k_nope, v = kv[..., :dm.nope], kv[..., dm.nope:]
    block = min(block, t)
    scale = (dm.nope + dm.rope) ** -0.5

    @jax.checkpoint
    def rows(args):
        i, qb = args
        s = (jnp.einsum("bqhn,bkhn->bhqk", qb[..., :dm.nope], k_nope)
             + jnp.einsum("bqhe,bke->bhqk", qb[..., dm.nope:], k_rope)
             ).astype(jnp.float32)
        pos = i * block + jnp.arange(block)
        s = jnp.where(pos[:, None] >= jnp.arange(t)[None, :], s * scale, NEG)
        p = jax.nn.softmax(s, -1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhv->bqhv", p, v)

    qb = q.reshape(b, t // block, block, h, -1).swapaxes(0, 1)
    o = lax.map(rows, (jnp.arange(t // block), qb))
    return o.swapaxes(0, 1).reshape(b, t, h * dm.vd) @ a["wo"]


def block(dm: Dims, lp, h, attn_block: int = 256):
    x = rms_norm(h, lp["ln1"], dm.eps)
    h = h + (kda(dm, lp["attn"], x) if "wqkv" in lp["attn"]
             else attention(dm, lp["attn"], x, attn_block))
    x = rms_norm(h, lp["ln2"], dm.eps)
    return h + (moe(dm, lp["moe"], x) if "moe" in lp
                else swiglu(lp["mlp"], x))


# ----------------------------------------------------------------- serving

@functools.partial(jax.jit, static_argnums=(2, 3))
def _serve_embed(tok, tokens, dtype, quantize):
    return _cast(quantize, dtype)(tok)[tokens]


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _serve_layer(dm: Dims, lp, h, dtype, quantize):
    return block(dm, jax.tree.map(_cast(quantize, dtype), lp), h)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _serve_head(dm: Dims, emb, h, dtype, quantize):
    return logits(dm, jax.tree.map(_cast(quantize, dtype), emb), h[:, -1])


def last_logits(dm: Dims, weights, tokens, dtype=jnp.float32,
                quantize=None):
    """Float32 logits after the last position of each prompt, tokens
    [B, T], computed one layer at a time, in the published order, from
    ``weights`` (the program's tree, any dtype) cast to ``dtype``.
    ``quantize`` maps each matrix first (the control's lower precision)."""
    h = _serve_embed(weights["emb"]["tok"], jnp.asarray(tokens), dtype,
                     quantize)
    at = {}
    for name, idx in dm.stacks().items():
        for j, i in enumerate(idx):
            at[i] = (name, j)
    for i in range(dm.layers):
        name, j = at[i]
        layer = jax.tree.map(lambda x: x[j], weights[name])
        h = _serve_layer(dm, layer, h, dtype, quantize)
    return _serve_head(dm, weights["emb"], h, dtype, quantize)
