"""Multi-head latent attention (MLA; DeepSeek-V2/V3, arXiv:2412.19437).

Queries come straight from the hidden state (no query latent):
``wq`` [d, H*(nope+rope)], each head's columns its ``nope`` part then its
``rope`` part.  Keys and values come from a ``kv_lora_rank`` latent:
``wkv_a`` [d, rank+rope] gives the latent (RMSNorm'd by ``kv_norm``) and
one RoPE key shared by every head; ``wkv_b`` [rank, H*(nope+v)] lifts the
latent to each head's key (``nope``) and value (``v``) columns.  Scores
are q_nope.k_nope + q_rope.k_rope over sqrt(nope + rope).  With
``mla_nope`` (Kimi Linear) nothing is rotated: the rope columns are plain
query and key columns, and attention sees no position but the causal
order.

The cache holds what a token adds: its normed latent and its RoPE'd key,
``[B, S, rank + rope]``.  Prefill lifts the latent to per-head keys and
values; decode folds ``wkv_b`` into the query and the output instead, so
it attends over the latent cache directly.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ArchConfig
from ..kernels import ref
from .layers import Params, _dense_init, rms_norm, rope


def init_mla(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> Params:
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rp, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {"wq": _dense_init(ks[0], d, H * (nope + rp), dtype),
            "wkv_a": _dense_init(ks[1], d, r + rp, dtype),
            "kv_norm": jnp.ones((r,), dtype),
            "wkv_b": _dense_init(ks[2], r, H * (nope + vd), dtype),
            "wo": _dense_init(ks[3], H * vd, d, dtype)}


def _project(cfg: ArchConfig, p: Params, x: jnp.ndarray,
             positions: jnp.ndarray):
    """x [B, T, d] -> q_nope [B,T,H,nope], q_rope [B,T,H,rope] and the
    cache entry [B, T, rank + rope] (normed latent, RoPE'd shared key;
    unrotated under ``mla_nope``)."""
    b, t, _ = x.shape
    H, r, nope = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = jnp.einsum("btd,dh->bth", x, p["wq"]).reshape(b, t, H, -1)
    # under mla_nope the rope columns stay, unrotated
    q_rope = (q[..., nope:] if cfg.mla_nope
              else rope(q[..., nope:], positions, cfg.rope_theta))
    kv = jnp.einsum("btd,dr->btr", x, p["wkv_a"])
    c = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_rope = (kv[..., r:] if cfg.mla_nope else
              rope(kv[..., None, r:], positions, cfg.rope_theta)[:, :, 0])
    return q[..., :nope], q_rope, jnp.concatenate([c, k_rope], -1)


def _scale(cfg: ArchConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def mla_prefill(cfg: ArchConfig, p: Params, x: jnp.ndarray,
                positions: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Causal self-attention over the whole prompt, x [B, T, d] (normed).
    Returns (out [B, T, d], cache entry [B, T, rank + rope])."""
    b, t, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    nope, rp, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("mla"):
        q_nope, q_rope, entry = _project(cfg, p, x, positions)
        kv = jnp.einsum("btr,rh->bth", entry[..., :r], p["wkv_b"]
                        ).reshape(b, t, H, nope + vd)
        q = jnp.concatenate([q_nope, q_rope], -1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(entry[:, :, None, r:], (b, t, H, rp))], -1)
        # the blockwise attention takes values as wide as the keys: zero
        # columns past v_head_dim add nothing and are cut off after
        v = jnp.pad(kv[..., nope:], ((0, 0), (0, 0), (0, 0),
                                     (0, nope + rp - vd)))
        out = ref.flash_attention(q[:, :, :, None], k, v)[:, :, :, 0, :vd]
        out = jnp.einsum("bth,hd->btd", out.reshape(b, t, H * vd), p["wo"])
    return out, entry


def mla_decode(cfg: ArchConfig, p: Params, x: jnp.ndarray,
               cache: jnp.ndarray, pos: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token per sequence, x [B, 1, d] (normed), at absolute position
    ``pos`` (scalar), against the latent cache [B, S, rank + rope] whose
    first ``pos`` slots are filled.  Returns (out [B, 1, d], new cache)."""
    b, smax = x.shape[0], cache.shape[1]
    H, r = cfg.n_heads, cfg.kv_lora_rank
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    with jax.named_scope("mla"):
        positions = jnp.full((b, 1), pos, jnp.int32)
        q_nope, q_rope, entry = _project(cfg, p, x, positions)
        cache = lax.dynamic_update_slice(cache, entry.astype(cache.dtype),
                                         (0, pos, 0))
        wkv_b = p["wkv_b"].reshape(r, H, nope + vd)
        # fold each head's key lift into its query: q_nope . (c W_k) = (q_nope
        # W_k^T) . c, so scores are taken against the latent itself
        q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, wkv_b[..., :nope])
        lat = cache[..., :r].astype(q_lat.dtype)
        s = (jnp.einsum("bqhr,bsr->bhqs", q_lat, lat,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhe,bse->bhqs", q_rope,
                          cache[..., r:].astype(q_rope.dtype),
                          preferred_element_type=jnp.float32)) * _scale(cfg)
        valid = jnp.arange(smax) <= pos
        s = jnp.where(valid[None, None, None], s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(lat.dtype)
        o_lat = jnp.einsum("bhqs,bsr->bqhr", w, lat)
        out = jnp.einsum("bqhr,rhv->bqhv", o_lat, wkv_b[..., nope:])
        out = jnp.einsum("bth,hd->btd", out.reshape(b, 1, H * vd), p["wo"])
    return out, cache
