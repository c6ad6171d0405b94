"""Shared transformer building blocks — pure functional JAX.

Parameters are plain pytrees (nested dicts of jnp arrays) so they stack
cleanly along a leading layer axis for ``lax.scan`` and take per-leaf
PartitionSpecs for pjit.  Projections are kept FUSED 2-D ([d, H*hd] etc.) so
the tensor-parallel axis divides them evenly for every assigned arch.

Conventions:
  x        [B, T, D]   activations (bf16)
  kv_cache [B, Smax, KV, hd] per layer (bf16 or int8+scale)
  positions[B, T]      absolute positions (for RoPE + causal masking)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ArchConfig

Params = Dict[str, Any]

# ---------------------------------------------------------------- initializers

def _dense_init(key, in_dim: int, out_dim: int, dtype) -> jnp.ndarray:
    scale = (2.0 / (in_dim + out_dim)) ** 0.5
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale
            ).astype(dtype)


def init_attention(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> Params:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": _dense_init(ks[0], d, H * hd, dtype),
        "wk": _dense_init(ks[1], d, KV * hd, dtype),
        "wv": _dense_init(ks[2], d, KV * hd, dtype),
        "wo": _dense_init(ks[3], H * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def init_mlp(d: int, f: int, key, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w1": _dense_init(ks[0], d, f, dtype),   # gate
        "w3": _dense_init(ks[1], d, f, dtype),   # up
        "w2": _dense_init(ks[2], f, d, dtype),   # down
    }


def init_block(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": init_attention(cfg, k1, dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "mlp": init_mlp(cfg.d_model, cfg.d_ff, k2, dtype),
    }


# ------------------------------------------------------------------- primitives

def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps)).astype(dt) * w


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [B, T, H, hd]; positions: [B, T]."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    gate = jax.nn.silu(jnp.einsum("btd,df->btf", x, p["w1"]))
    up = jnp.einsum("btd,df->btf", x, p["w3"])
    return jnp.einsum("btf,fd->btd", gate * up, p["w2"])


# ------------------------------------------------------------------- attention

def _split_heads(x: jnp.ndarray, n: int, hd: int) -> jnp.ndarray:
    b, t, _ = x.shape
    return x.reshape(b, t, n, hd)


def _tp_size() -> int:
    from ..parallel import ctx
    mesh = ctx.get_mesh()
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return 1
    return mesh.shape["model"]


def _pad_cols(w: jnp.ndarray, target: int) -> jnp.ndarray:
    return jnp.pad(w, ((0, 0), (0, target - w.shape[-1])))


def _qkv(cfg: ArchConfig, p: Params, x: jnp.ndarray, positions: jnp.ndarray,
         pad_tp: bool = False):
    """QKV projections (+RoPE, qk-norm, bias).

    ``pad_tp``: TP head padding (§Perf, qwen32#1).  When the head count
    does not divide the model axis (qwen32/minicpm: 36-40 MHA heads over
    16; phi3/arctic GQA), GSPMD degenerates to gathering whole attention
    tensors.  Padding the PROJECTION WEIGHTS with zero columns up to the
    next multiple of tp is mathematically exact (phantom heads' outputs
    hit zero rows of wo) and makes every reshape/shard boundary even.
    GQA-uneven archs additionally expand k/v per-q-head locally
    (kv weights are small), turning attention into even MHA layout."""
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    tp = _tp_size() if pad_tp else 1
    need = tp > 1 and (H % tp != 0 or KV % tp != 0)
    Hp = (H + tp - 1) // tp * tp if need else H
    mha = KV == H

    wq = _pad_cols(p["wq"], Hp * hd) if Hp != H else p["wq"]
    q = jnp.einsum("btd,dh->bth", x, wq)
    if need and mha:
        wk = _pad_cols(p["wk"], Hp * hd)
        wv = _pad_cols(p["wv"], Hp * hd)
    else:
        wk, wv = p["wk"], p["wv"]
    k = jnp.einsum("btd,dh->bth", x, wk)
    v = jnp.einsum("btd,dh->bth", x, wv)
    if cfg.qkv_bias:
        bq = (jnp.pad(p["bq"], (0, (Hp - H) * hd)) if Hp != H else p["bq"])
        bkv_pad = (Hp - H) * hd if (need and mha) else 0
        q = q + bq
        k = k + (jnp.pad(p["bk"], (0, bkv_pad)) if bkv_pad else p["bk"])
        v = v + (jnp.pad(p["bv"], (0, bkv_pad)) if bkv_pad else p["bv"])
    q = _split_heads(q, Hp, hd)
    kv_n = Hp if (need and mha) else KV
    k = _split_heads(k, kv_n, hd)
    v = _split_heads(v, kv_n, hd)
    if need and not mha:
        # GQA-uneven: expand kv per padded q head (local; kv is replicated)
        qmap = jnp.minimum(jnp.arange(Hp) // max(H // KV, 1), KV - 1)
        k = jnp.take(k, qmap, axis=2)
        v = jnp.take(v, qmap, axis=2)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(cfg: ArchConfig, q, k, v, q_pos, k_pos, k_valid=None):
    """Grouped-query scaled-dot-product attention with causal (+SWA) mask.

    q [B,Tq,H,hd], k/v [B,Tk,KV,hd]; *_pos absolute positions [B,Tq]/[B,Tk].
    k_valid: optional [B,Tk] bool (cache entries actually written)."""
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    G = H // KV
    b, tq = q.shape[0], q.shape[1]
    tk = k.shape[1]
    qg = q.reshape(b, tq, KV, G, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k).astype(jnp.float32)
    logits = logits / (hd ** 0.5)
    causal = q_pos[:, None, None, :, None] >= k_pos[:, None, None, None, :]
    mask = causal
    if cfg.swa_window:
        near = (q_pos[:, None, None, :, None]
                - k_pos[:, None, None, None, :]) < cfg.swa_window
        mask = jnp.logical_and(mask, near)
    if k_valid is not None:
        mask = jnp.logical_and(mask, k_valid[:, None, None, None, :])
    logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(b, tq, H * hd)


def attention(cfg: ArchConfig, p: Params, x: jnp.ndarray,
              positions: jnp.ndarray) -> jnp.ndarray:
    """Full self-attention over x (train / prefill)."""
    q, k, v = _qkv(cfg, p, x, positions)
    out = _sdpa(cfg, q, k, v, positions, positions)
    return jnp.einsum("bth,hd->btd", out, p["wo"])


def attention_decode(cfg: ArchConfig, p: Params, x: jnp.ndarray,
                     cache_k: jnp.ndarray, cache_v: jnp.ndarray,
                     write_pos: jnp.ndarray, q_pos: jnp.ndarray,
                     n_valid: jnp.ndarray,
                     kv_scale: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None):
    """One-token decode against a KV cache (ring buffer for SWA).

    x [B,1,D]; cache_k/v [B,Smax,KV,hd] (bf16, or int8 with kv_scale);
    write_pos: slot to write (== q_pos for full attn, q_pos % window for SWA);
    q_pos: absolute position of the new token (RoPE);
    n_valid: number of populated cache slots AFTER this write.
    Keys are cached post-RoPE, so relative attention stays correct for the
    ring buffer.  Returns (out [B,1,D], new_k, new_v, new_scales)."""
    b, _, _ = x.shape
    smax = cache_k.shape[1]
    positions = jnp.full((b, 1), q_pos, dtype=jnp.int32)
    q, k_new, v_new = _qkv(cfg, p, x, positions)

    slot = jnp.broadcast_to(jnp.arange(smax, dtype=jnp.int32)[None, :],
                            (b, smax))
    k_valid = slot < n_valid
    # with n_valid == q_pos+1 (full attention) the causal mask reduces to
    # the validity mask, and for the SWA ring buffer validity IS the mask.
    if kv_scale is not None:
        ks, vs = kv_scale
        k_q, k_s = _quantize_kv(k_new)
        v_q, v_s = _quantize_kv(v_new)
        cache_k = lax.dynamic_update_slice(cache_k, k_q, (0, write_pos, 0, 0))
        cache_v = lax.dynamic_update_slice(cache_v, v_q, (0, write_pos, 0, 0))
        ks = lax.dynamic_update_slice(ks, k_s, (0, write_pos, 0, 0))
        vs = lax.dynamic_update_slice(vs, v_s, (0, write_pos, 0, 0))
        new_scales = (ks, vs)
        # int8 attention with scales applied POST-dot ((q·k_q)·s_k == q·(k_q·s_k)
        # since the scale is per (token, head)): the int8->bf16 converts fuse
        # into the matmuls — the dequantized cache is NEVER materialized
        # (§Perf qwen32-decode#1).
        H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        G = H // KV
        qg = q.reshape(b, 1, KV, G, hd)
        s = jnp.einsum("bqkgh,bskh->bkgqs", qg,
                       cache_k.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        s = s * ks[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
        s = s / (hd ** 0.5)
        s = jnp.where(k_valid[:, None, None, None, :], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        pv = (pr * vs[..., 0].transpose(0, 2, 1)[:, :, None, None, :]
              ).astype(jnp.bfloat16)
        outh = jnp.einsum("bkgqs,bskh->bqkgh", pv,
                          cache_v.astype(jnp.bfloat16))
        out = outh.reshape(b, 1, H * hd)
    else:
        cache_k = lax.dynamic_update_slice(
            cache_k, k_new.astype(cache_k.dtype), (0, write_pos, 0, 0))
        cache_v = lax.dynamic_update_slice(
            cache_v, v_new.astype(cache_v.dtype), (0, write_pos, 0, 0))
        new_scales = None
        out = _sdpa(cfg, q, cache_k, cache_v,
                    jnp.zeros((b, 1), jnp.int32), jnp.zeros_like(slot),
                    k_valid)
    out = jnp.einsum("bth,hd->btd", out, p["wo"])
    return out, cache_k, cache_v, new_scales


def _quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per (token, head) symmetric int8 quantization along hd."""
    scale = (jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
             / 127.0 + 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def prefill_kv(cfg: ArchConfig, p: Params, x: jnp.ndarray,
               positions: jnp.ndarray, smax: int, kv_dtype=jnp.bfloat16):
    """Forward over a full prompt, returning output AND the populated cache
    (padded to smax)."""
    b, t, _ = x.shape
    q, k, v = _qkv(cfg, p, x, positions)
    out = _sdpa(cfg, q, k, v, positions, positions)
    out = jnp.einsum("bth,hd->btd", out, p["wo"])
    pad = [(0, 0), (0, smax - t), (0, 0), (0, 0)]
    if kv_dtype == jnp.int8:
        k_q, k_s = _quantize_kv(k)
        v_q, v_s = _quantize_kv(v)
        cache = (jnp.pad(k_q, pad), jnp.pad(v_q, pad),
                 jnp.pad(k_s, pad), jnp.pad(v_s, pad))
    else:
        cache = (jnp.pad(k.astype(kv_dtype), pad),
                 jnp.pad(v.astype(kv_dtype), pad), None, None)
    return out, cache


# ------------------------------------------------------------------- embeddings

def init_embeddings(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> Params:
    V = padded_vocab(cfg)
    k1, k2 = jax.random.split(key)
    p = {"tok": (jax.random.normal(k1, (V, cfg.d_model), jnp.float32) * 0.02
                 ).astype(dtype),
         "ln_f": jnp.ones((cfg.d_model,), dtype)}
    if not cfg.tie_embeddings:
        p["out"] = _dense_init(k2, cfg.d_model, V, dtype)
    return p


def padded_vocab(cfg: ArchConfig) -> int:
    return (cfg.vocab + 255) // 256 * 256


def embed(p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["tok"], tokens, axis=0)


def unembed(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x = rms_norm(x, p["ln_f"], eps)
    if "out" in p:
        return jnp.einsum("btd,dv->btv", x, p["out"])
    return jnp.einsum("btd,vd->btv", x, p["tok"])


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  vocab: int) -> jnp.ndarray:
    """fp32 softmax CE, ignoring padded vocab entries.

    Written as iota-onehot reductions (NOT take_along_axis): gather/scatter
    over the vocab axis would force GSPMD to materialize an UNSHARDED
    [B, T, V] gradient; elementwise+reduce keeps everything vocab-sharded."""
    logits = logits.astype(jnp.float32)
    vocab_ids = lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    if logits.shape[-1] > vocab:
        logits = jnp.where(vocab_ids < vocab, logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    logz = jnp.log(jnp.sum(jnp.exp(logits - lax.stop_gradient(m)),
                           axis=-1)) + m[..., 0]
    onehot = (vocab_ids == labels[..., None]).astype(jnp.float32)
    gold = jnp.sum(logits * onehot, axis=-1)
    return jnp.mean(logz - gold)
