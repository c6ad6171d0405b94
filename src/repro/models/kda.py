"""Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692).

Per layer, on the normed input x [B, T, d], with H heads of K channels
(HK = H * K) and gates of rank R:

  q, k, v = SiLU(ShortConv(x W_qkv)), split in three, each [B, T, H, K];
            ShortConv a depthwise causal conv of ``kda_conv`` over time;
            q and k L2-normalised per head, q scaled by K^-1/2
  b       = sigmoid(x W_b)                                   [B, T, H]
  g       = -exp(A_log) softplus(x W_fa W_fb + dt_bias)      [B, T, H, K]
  o       = the gated delta rule over (q, k, v, g, b) (``kernels/ref.py``)
  out     = W_o [RMSNorm_head(o) * sigmoid(x W_ga W_gb + g_bias)]

Prefill runs the chunked form from a zero state; decode runs one step of
the recurrence.  What a sequence carries from token to token is the state
[B, H, K, K] (float32) and the conv's last ``kda_conv - 1`` inputs
[B, kda_conv - 1, 3 HK] (the projection's output, in its own type).
Device scopes: ``kda.conv``, ``kda.gates``, ``kda.chunk``, ``kda.state``
(the last two inside ``ref.kda_chunked``) and ``kda.out``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ArchConfig
from ..kernels import ref
from .layers import Params, _dense_init, rms_norm


def init_kda(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> Params:
    d, H, K = cfg.d_model, cfg.kda_heads, cfg.kda_head_dim
    hk, r = H * K, cfg.kda_gate_rank
    ks = jax.random.split(key, 9)
    # decays as Mamba draws them: dt in [1e-3, 1e-1], A in [1, 16]
    dt = jnp.exp(jax.random.uniform(ks[5], (hk,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "wqkv": _dense_init(ks[0], d, 3 * hk, dtype),
        "conv": (jax.random.normal(ks[1], (cfg.kda_conv, 3 * hk),
                                   jnp.float32)
                 * cfg.kda_conv ** -0.5).astype(dtype),
        "wb": _dense_init(ks[2], d, H, dtype),
        "wf_a": _dense_init(ks[3], d, r, dtype),
        "wf_b": _dense_init(ks[4], r, hk, dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(ks[6], (H,), jnp.float32, 1.0,
                                            16.0)).astype(dtype),
        "wg_a": _dense_init(ks[7], d, r, dtype),
        "wg_b": _dense_init(ks[8], r, hk, dtype),
        "g_bias": jnp.zeros((hk,), dtype),
        "o_norm": jnp.ones((K,), dtype),
        "wo": _dense_init(jax.random.fold_in(key, 9), hk, d, dtype),
    }


def _l2(x: jnp.ndarray) -> jnp.ndarray:
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _inputs(cfg: ArchConfig, p: Params, x: jnp.ndarray, tail: jnp.ndarray):
    """x [B, T, d] and the conv's previous inputs tail [B, kda_conv-1, 3HK]
    -> float32 (q, k, v, g, b, gate) and the new tail."""
    b, t, _ = x.shape
    H, K = cfg.kda_heads, cfg.kda_head_dim
    hk = H * K
    with jax.named_scope("kda.conv"):
        # q, k and v one at a time, so that one's float32 temporaries are
        # alive at once, not three's
        qkv, tails = [], []
        for i in range(3):
            cols = slice(i * hk, (i + 1) * hk)
            z = jnp.einsum("btd,dc->btc", x, p["wqkv"][:, cols])
            zz = jnp.concatenate([tail[..., cols].astype(z.dtype), z], 1)
            w = p["conv"][:, cols].astype(jnp.float32)
            y = sum(w[j] * zz[:, j:j + t].astype(jnp.float32)
                    for j in range(cfg.kda_conv))
            qkv.append(jax.nn.silu(y).reshape(b, t, H, K))
            tails.append(zz[:, t:])
        q, k, v = qkv
        q, k = _l2(q) * K ** -0.5, _l2(k)
    with jax.named_scope("kda.gates"):
        beta = jax.nn.sigmoid(
            jnp.einsum("btd,dh->bth", x, p["wb"]).astype(jnp.float32))
        f = jnp.einsum("btr,rc->btc", jnp.einsum("btd,dr->btr", x, p["wf_a"]),
                       p["wf_b"]).astype(jnp.float32)
        f = (f + p["dt_bias"].astype(jnp.float32)).reshape(b, t, H, K)
        g = (-jnp.exp(p["A_log"].astype(jnp.float32))[:, None]
             * jax.nn.softplus(f))
        gate = jnp.einsum("btr,rc->btc",
                          jnp.einsum("btd,dr->btr", x, p["wg_a"]),
                          p["wg_b"]).astype(jnp.float32)
        gate = jax.nn.sigmoid(gate + p["g_bias"].astype(jnp.float32))
    return q, k, v, g, beta, gate, jnp.concatenate(tails, -1)


def _out(cfg: ArchConfig, p: Params, o: jnp.ndarray, gate: jnp.ndarray,
         dtype) -> jnp.ndarray:
    b, t = o.shape[:2]
    with jax.named_scope("kda.out"):
        o = rms_norm(o, p["o_norm"], cfg.norm_eps).reshape(b, t, -1) * gate
        return jnp.einsum("btc,cd->btd", o.astype(dtype), p["wo"])


def kda_prefill(cfg: ArchConfig, p: Params, x: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The whole prompt, x [B, T, d] (normed), from a zero state.  Returns
    (out [B, T, d], state [B, H, K, K] float32, conv tail)."""
    b = x.shape[0]
    H, K = cfg.kda_heads, cfg.kda_head_dim
    tail = jnp.zeros((b, cfg.kda_conv - 1, 3 * H * K), x.dtype)
    q, k, v, g, beta, gate, tail = _inputs(cfg, p, x, tail)
    o, state = ref.kda_chunked(q, k, v, g, beta,
                               jnp.zeros((b, H, K, K), jnp.float32),
                               chunk=cfg.kda_chunk)
    return _out(cfg, p, o, gate, x.dtype), state, tail


def kda_decode(cfg: ArchConfig, p: Params, x: jnp.ndarray,
               state: jnp.ndarray, tail: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One token per sequence, x [B, 1, d] (normed), after ``state`` and
    the conv ``tail``.  Returns (out [B, 1, d], state, tail)."""
    q, k, v, g, beta, gate, tail = _inputs(cfg, p, x, tail)
    state, o = ref.kda_step(state, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                            beta[:, 0])
    return _out(cfg, p, o[:, None], gate, x.dtype), state, tail
