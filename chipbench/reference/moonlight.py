"""Plain reference of Moonlight-16B-A3B's block, one chip's share of it.

Straight ``jax.numpy`` from the configuration file's sizes; it imports
nothing of the program.  The DeepSeek-V3 block (arXiv:2412.19437) as
Moonlight's config.json sets it:

  * RMSNorm (eps ``rms_norm_eps``) before attention and before the FFN,
    residual adds, a final RMSNorm and an untied head;
  * latent attention: q = x Wq per head [nope | rope]; [c | k_rope] =
    x Wkv_a, c RMSNorm'd; per head [k_nope | v] = c Wkv_b; RoPE on
    q_rope and on the one k_rope all heads share; scores q_nope.k_nope +
    q_rope.k_rope over sqrt(nope + rope), causal softmax, o Wo;
  * layers before ``first_k_dense_replace``: a SwiGLU of
    ``intermediate_size``;
  * the others: sigmoid scores of x Wr over all ``n_routed_experts``;
    the top ``num_experts_per_tok`` of score + correction bias (one group,
    ``noaux_tc``); weights the chosen scores, normalised and times
    ``routed_scaling_factor``; each chosen expert a SwiGLU of
    ``moe_intermediate_size``; plus the shared experts, one SwiGLU of
    ``n_shared_experts`` x ``moe_intermediate_size``.

Departures from the published model, also under the file's
``departures``:

  * the chip's share (``deployment``): only the held experts
    ``[lo, lo + n_routed_experts_held)`` add their part; the others lie
    on other chips and add nothing.  Routing still runs over all experts;
  * RoPE rotates the two halves of the 64-wide RoPE part (the published
    code rotates interleaved pairs): with random weights, a fixed
    permutation of the RoPE columns of Wq and Wkv_a;
  * random weights from the seed, the correction bias among them
    (``e_score_correction_bias_scale`` x N(0, 1); published: learned).

Everything runs in ``dtype``: float32 at "highest" matmul precision is the
reference, a lower one the control.  The whole causal forward runs with
no cache, one layer at a time, attention over blocks of query rows, and
each held expert densely over every token, weighted by its gate (zero
where not chosen), so that it fits beside the weights on one chip.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

NEG = -1e30


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
    vocab: int
    vocab_rows: int
    theta: float
    eps: float
    routed_scale: float
    bias_scale: float

    @classmethod
    def of(cls, spec: Dict[str, Any]) -> "Dims":
        if (spec["scoring_func"], spec["topk_method"], spec["n_group"],
                spec["topk_group"], spec["norm_topk_prob"],
                spec["q_lora_rank"]) != ("sigmoid", "noaux_tc", 1, 1, True,
                                         None):
            raise ValueError("not the block this reference computes")
        pad = spec["vocab_padded_to"]
        lo, hi = spec["deployment"]["held_experts"]
        if hi - lo != spec["n_routed_experts_held"]:
            raise ValueError("held_experts does not match "
                             "n_routed_experts_held")
        return cls(d=spec["hidden_size"], heads=spec["num_attention_heads"],
                   nope=spec["qk_nope_head_dim"],
                   rope=spec["qk_rope_head_dim"], vd=spec["v_head_dim"],
                   rank=spec["kv_lora_rank"], ff=spec["intermediate_size"],
                   fe=spec["moe_intermediate_size"],
                   experts=spec["n_routed_experts"],
                   held=spec["n_routed_experts_held"], lo=lo,
                   top_k=spec["num_experts_per_tok"],
                   shared=spec["n_shared_experts"],
                   dense_layers=spec["first_k_dense_replace"],
                   layers=spec["num_hidden_layers"],
                   vocab=spec["vocab_size"],
                   vocab_rows=(spec["vocab_size"] + pad - 1) // pad * pad,
                   theta=float(spec["rope_theta"]), eps=spec["rms_norm_eps"],
                   routed_scale=spec["routed_scaling_factor"],
                   bias_scale=spec["e_score_correction_bias_scale"])


# ----------------------------------------------------------------- weights

def _dense(key, fan_in: int, fan_out: int, lead=()):
    return (jax.random.normal(key, lead + (fan_in, fan_out), jnp.float32)
            * (2.0 / (fan_in + fan_out)) ** 0.5)


def _norm(key, n: int):
    """A norm weight at 1 + N(0, 0.1), so that one applied wrong shows."""
    return 1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)


def _swiglu_weights(key, d: int, f: int, lead=()):
    k = jax.random.split(key, 3)
    return {"w1": _dense(k[0], d, f, lead), "w3": _dense(k[1], d, f, lead),
            "w2": _dense(k[2], f, d, lead)}


def _layer(dm: Dims, key, moe: bool) -> Dict[str, Any]:
    k = jax.random.split(key, 10)
    h = dm.heads
    attn = {"wq": _dense(k[0], dm.d, h * (dm.nope + dm.rope)),
            "wkv_a": _dense(k[1], dm.d, dm.rank + dm.rope),
            "kv_norm": _norm(k[2], dm.rank),
            "wkv_b": _dense(k[3], dm.rank, h * (dm.nope + dm.vd)),
            "wo": _dense(k[4], h * dm.vd, dm.d)}
    out = {"ln1": _norm(k[5], dm.d), "attn": attn, "ln2": _norm(k[6], dm.d)}
    if not moe:
        out["mlp"] = _swiglu_weights(k[7], dm.d, dm.ff)
        return out
    m = _swiglu_weights(k[7], dm.d, dm.fe, (dm.held,))
    m["router"] = _dense(k[8], dm.d, dm.experts)
    m["router_bias"] = dm.bias_scale * jax.random.normal(
        k[9], (dm.experts,), jnp.float32)
    m["shared"] = _swiglu_weights(jax.random.fold_in(k[7], 1), dm.d,
                                  dm.shared * dm.fe)
    out["moe"] = m
    return out


def serve_weights(dm: Dims, seed: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The share's serving weights from the seed, in the program's tree
    (``emb``; ``dense``, the leading dense layers stacked; ``layers``, the
    MoE layers stacked), in the type they are served in."""
    k_emb, k_dense, k_moe = jax.random.split(jax.random.PRNGKey(seed), 3)
    ke = jax.random.split(k_emb, 3)
    moe_layers = dm.layers - dm.dense_layers
    w = {"emb": {"tok": jax.random.normal(ke[0], (dm.vocab_rows, dm.d),
                                          jnp.float32) * 0.02,
                 "ln_f": _norm(ke[1], dm.d),
                 "out": _dense(ke[2], dm.d, dm.vocab_rows)},
         "dense": jax.vmap(lambda k: _layer(dm, k, False))(
             jax.random.split(k_dense, dm.dense_layers)),
         "layers": jax.vmap(lambda k: _layer(dm, k, True))(
             jax.random.split(k_moe, moe_layers))}
    return jax.tree.map(lambda x: x.astype(dtype), w)


# ----------------------------------------------------------------- model

def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return y.astype(x.dtype) * w


def rope(x, theta):
    """x [B, T, H, n] at positions 0..T-1; rotate the two halves."""
    t, n = x.shape[1], x.shape[-1]
    half = n // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def swiglu(m, x):
    return (jax.nn.silu(x @ m["w1"]) * (x @ m["w3"])) @ m["w2"]


def attention(dm: Dims, a, x, block: int):
    """Latent attention over x [B, T, d] (normed), causal, in blocks of
    ``block`` query rows."""
    b, t, _ = x.shape
    h, r = dm.heads, dm.rank
    q = (x @ a["wq"]).reshape(b, t, h, dm.nope + dm.rope)
    q_nope, q_rope = q[..., :dm.nope], rope(q[..., dm.nope:], dm.theta)
    ckv = x @ a["wkv_a"]
    c = rms_norm(ckv[..., :r], a["kv_norm"], dm.eps)
    k_rope = rope(ckv[:, :, None, r:], dm.theta)[:, :, 0]       # [B, T, rope]
    kv = (c @ a["wkv_b"]).reshape(b, t, h, dm.nope + dm.vd)
    k_nope, v = kv[..., :dm.nope], kv[..., dm.nope:]
    block = min(block, t)
    scale = (dm.nope + dm.rope) ** -0.5

    @jax.checkpoint
    def rows(args):
        i, qn, qr = args
        s = (jnp.einsum("bqhn,bkhn->bhqk", qn, k_nope)
             + jnp.einsum("bqhe,bke->bhqk", qr, k_rope)).astype(jnp.float32)
        pos = i * block + jnp.arange(block)
        s = jnp.where(pos[:, None] >= jnp.arange(t)[None, :], s * scale, NEG)
        p = jax.nn.softmax(s, -1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhv->bqhv", p, v)

    def by_block(z):
        return z.reshape(b, t // block, block, *z.shape[2:]).swapaxes(0, 1)

    o = lax.map(rows, (jnp.arange(t // block), by_block(q_nope),
                       by_block(q_rope)))
    o = o.swapaxes(0, 1).reshape(b, t, h * dm.vd)
    return o @ a["wo"]


def gates(dm: Dims, m, x):
    """[..., d] -> [..., experts]: each token's routed weight of every
    expert, zero for those not chosen."""
    scores = jax.nn.sigmoid((x @ m["router"]).astype(jnp.float32))
    _, top = lax.top_k(scores + m["router_bias"].astype(jnp.float32),
                       dm.top_k)
    chosen = jnp.sum(jax.nn.one_hot(top, dm.experts, dtype=jnp.float32),
                     -2)
    w = scores * chosen
    return w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * dm.routed_scale


def moe(dm: Dims, m, x):
    """The held experts' part for every token, plus the shared experts."""
    g = gates(dm, m, x).astype(x.dtype)
    out = swiglu(m["shared"], x)
    for e in range(dm.held):
        expert = jax.tree.map(lambda w: w[e],
                              {k: m[k] for k in ("w1", "w3", "w2")})
        out = out + g[..., dm.lo + e, None] * swiglu(expert, x)
    return out


def block(dm: Dims, lp, h, attn_block: int = 256):
    h = h + attention(dm, lp["attn"], rms_norm(h, lp["ln1"], dm.eps),
                      attn_block)
    x = rms_norm(h, lp["ln2"], dm.eps)
    return h + (moe(dm, lp["moe"], x) if "moe" in lp
                else swiglu(lp["mlp"], x))


def logits(dm: Dims, emb, h):
    """[..., d] -> [..., vocab_rows] float32; ids >= vocab masked out."""
    z = (rms_norm(h, emb["ln_f"], dm.eps) @ emb["out"]).astype(jnp.float32)
    return jnp.where(jnp.arange(dm.vocab_rows) < dm.vocab, z, NEG)


# ----------------------------------------------------------------- serving

def _cast(quantize, dtype):
    def cast(x):
        if x.ndim > 1 and quantize is not None:
            x = quantize(x)
        return x.astype(dtype)
    return cast


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
    [B, T], computed one layer at a time from ``weights`` (the program's
    tree, any dtype) cast to ``dtype``.  ``quantize`` maps each matrix
    first (the control's lower precision)."""
    h = _serve_embed(weights["emb"]["tok"], jnp.asarray(tokens), dtype,
                     quantize)
    for stack in ("dense", "layers"):
        for i in range(jax.tree.leaves(weights[stack])[0].shape[0]):
            layer = jax.tree.map(lambda x: x[i], weights[stack])
            h = _serve_layer(dm, layer, h, dtype, quantize)
    return _serve_head(dm, weights["emb"], h, dtype, quantize)
