"""Pallas TPU flash-attention forward kernel.

TPU-native structure: grid (batch·kv_heads·groups, q_blocks, kv_blocks) with
the kv axis INNERMOST so the online-softmax running state (m, l, acc) lives
in VMEM scratch across kv steps; every BlockSpec tile is VMEM-resident and
MXU-aligned (block_q × head_dim and block_k × head_dim tiles, multiples of
128 on the matmul dims for full systolic utilization).

Validated in interpret mode against ``ref.attention_naive`` /
``ref.flash_attention`` (see tests/test_kernels_pallas.py); the ref module
is also the custom-VJP autodiff path — this kernel is the TPU fwd hot loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q: int, block_k: int, n_kv: int, seq_q: int,
                  seq_k: int, window: int, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                    # [bk, hd]
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = (q_pos >= k_pos) & (k_pos < seq_k) & (q_pos < seq_q)
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        window: int = 0, block_q: int = 128,
                        block_k: int = 128,
                        *, interpret: bool) -> jnp.ndarray:
    """q [B,Tq,KV,G,hd]; k/v [B,Tk,KV,hd] -> [B,Tq,KV,G,hd] (causal)."""
    b, tq, kvh, g, hd = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    pq = (-tq) % block_q
    pk = (-tk) % block_k
    # fold (b, kv, g) into one leading grid axis; k/v broadcast over g
    qf = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
    qf = qf.transpose(0, 2, 3, 1, 4).reshape(b * kvh * g, tq + pq, hd)
    kf = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    kf = jnp.broadcast_to(kf.transpose(0, 2, 1, 3)[:, :, None],
                          (b, kvh, g, tk + pk, hd)
                          ).reshape(b * kvh * g, tk + pk, hd)
    vf = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vf = jnp.broadcast_to(vf.transpose(0, 2, 1, 3)[:, :, None],
                          (b, kvh, g, tk + pk, hd)
                          ).reshape(b * kvh * g, tk + pk, hd)
    nq = (tq + pq) // block_q
    nk = (tk + pk) // block_k

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, n_kv=nk,
        seq_q=tq, seq_k=tk, window=window, scale=1.0 / (hd ** 0.5))
    out = pl.pallas_call(
        kernel,
        grid=(b * kvh * g, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((1, block_k, hd), lambda i, j, kk: (i, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda i, j, kk: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kvh * g, tq + pq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),        # m
            pltpu.VMEM((block_q,), jnp.float32),        # l
            pltpu.VMEM((block_q, hd), jnp.float32),     # acc
        ],
        interpret=interpret,
    )(qf, kf, vf)
    out = out[:, :tq].reshape(b, kvh, g, tq, hd).transpose(0, 3, 1, 2, 4)
    return out
