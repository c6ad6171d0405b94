"""Pallas TPU kernel for the chunked WKV6 recurrence (RWKV6 "Finch").

Grid: (B·H, n_chunks) with the chunk axis innermost-sequential; the
[K, V] state matrix lives in VMEM scratch, held transposed as [V, K] so its
per-key decay is a row broadcast, and carries across chunk steps —
the TPU adaptation of the CUDA kernel the RWKV authors ship: instead of one
thread-block per (b,h) marching token-by-token, each grid step does a
chunk's worth of MXU matmuls (pairwise-decay intra-chunk term) plus one
rank-c state update, so the VPU/MXU stay busy and HBM traffic is O(T·K)
instead of O(T·K·V).

Oracle: ``ref.rwkv6_chunked`` (itself validated against the per-step naive
recurrence and autodiff)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# full-f32 MXU passes: the default rounds f32 operands to bf16, which the
# chunk's exponentiated decays amplify past the oracle's 3e-3 tolerance
_F32 = jax.lax.Precision.HIGHEST


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, st_ref, *,
                 chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        st_ref[...] = jnp.zeros_like(st_ref)

    r = r_ref[0].astype(jnp.float32)          # [c, K]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)          # [c, V]
    w = w_ref[0].astype(jnp.float32)          # [c, K] in (0,1)
    u = u_ref[0].astype(jnp.float32)          # [1, K] bonus

    logw = jnp.log(jnp.maximum(w, 1e-30))
    # [i, j, K] position grids (3-D iotas: Mosaic cannot reshape i1 masks)
    shape3 = (chunk, chunk, logw.shape[1])
    row3 = jax.lax.broadcasted_iota(jnp.int32, shape3, 0)
    col3 = jax.lax.broadcasted_iota(jnp.int32, shape3, 1)
    # inclusive prefix sum over the chunk as a product with a lower-
    # triangular ones matrix (masked reduction: exact f32)
    cl = jnp.sum(jnp.where(row3 >= col3, logw[None, :, :], 0.0),
                 axis=1)                      # inclusive [c, K]
    cl_prev = cl - logw                       # exclusive
    cl_last = jnp.sum(logw, axis=0, keepdims=True)              # [1, K]

    St = st_ref[...]                          # [V, K] (state, transposed)
    # state contribution: y_state[t] = (r_t ⊙ e^{cl_prev_t}) @ S
    y_state = jax.lax.dot_general(r * jnp.exp(cl_prev), St,
                                  (((1,), (1,)), ((), ())), precision=_F32,
                                  preferred_element_type=jnp.float32)
    # intra-chunk: att[i,j] = Σ_k r_i e^{cl_prev_i - cl_j} k_j   (j < i)
    diff = cl_prev[:, None, :] - cl[None, :, :]          # [c, c, K]
    D = jnp.where(row3 > col3, jnp.exp(jnp.minimum(diff, 30.0)), 0.0)
    att = jnp.sum(r[:, None, :] * D * k[None, :, :], axis=2)  # [c, c]
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)          # u-bonus [c, 1]
    y = y_state + jax.lax.dot_general(
        att, v, (((1,), (0,)), ((), ())),
        precision=_F32, preferred_element_type=jnp.float32) + diag * v
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: S' = e^{cl_last} ⊙ S + Σ_j e^{cl_last - cl_j} k_j v_j^T
    carry_w = jnp.exp(jnp.minimum(cl_last - cl, 30.0))  # [c, K]
    st_ref[...] = (jnp.exp(cl_last) * St
                   + jax.lax.dot_general(
                       v, carry_w * k, (((0,), (0,)), ((), ())),
                       precision=_F32, preferred_element_type=jnp.float32))


def wkv6_fwd(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
             u: jnp.ndarray, chunk: int = 64, *,
             interpret: bool) -> jnp.ndarray:
    """r,k,w [B,T,H,K]; v [B,T,H,V]; u [H,K] -> y [B,T,H,V] (zero init state)."""
    b, t, h, kd = r.shape
    vd = v.shape[-1]
    chunk = min(chunk, t)
    pad = (-t) % chunk
    if pad:
        r, k = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                for a in (r, k))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0), (0, 0)),
                    constant_values=1.0)
    tp = t + pad
    nt = tp // chunk

    def fold(a, d):
        return a.transpose(0, 2, 1, 3).reshape(b * h, tp, d)
    rf, kf, wf = fold(r, kd), fold(k, kd), fold(w, kd)
    vf = fold(v, vd)
    uf = jnp.broadcast_to(u[None], (b, h, kd)).reshape(b * h, 1, kd)

    kernel = functools.partial(_wkv6_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(b * h, nt),
        in_specs=[
            pl.BlockSpec((1, chunk, kd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, kd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, vd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, kd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, kd), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, vd), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tp, vd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((vd, kd), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, uf)
    return y[:, :t].reshape(b, h, t, vd).transpose(0, 2, 1, 3)
