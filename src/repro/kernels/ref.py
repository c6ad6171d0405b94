"""Pure-jnp reference implementations (oracles) for every Pallas kernel.

These are ALSO the implementations the models lower through on CPU: they are
memory-bounded (blockwise flash attention, chunked scans) so the models'
programs keep a production memory shape, and the Pallas kernels are
validated against them in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


# =============================================================== flash attention
#
# Blockwise causal attention with online softmax AND a flash-style custom
# VJP: the backward RECOMPUTES logit tiles from (q, k, lse) instead of
# letting autodiff save every [Bq, Bk] probability tile of every scan step
# (which would resurrect the O(T^2) memory that flash exists to avoid).


def _mask_for(q_pos, k_pos, tk, window):
    mask = q_pos[:, None] >= k_pos[None, :]
    mask = jnp.logical_and(mask, (k_pos < tk)[None, :])
    if window:
        mask = jnp.logical_and(mask,
                               (q_pos[:, None] - k_pos[None, :]) < window)
    return mask


def _kv_slice(kp, vp, q_start, j, tk, window, span, block_k):
    if window:
        k_start = jnp.clip(q_start - window + 1, 0, max(tk - span, 0))
        k_j = lax.dynamic_slice_in_dim(kp, k_start, span, axis=1)
        v_j = lax.dynamic_slice_in_dim(vp, k_start, span, axis=1)
        k_pos = k_start + jnp.arange(span)
    else:
        k_j = lax.dynamic_slice_in_dim(kp, j * block_k, block_k, axis=1)
        v_j = lax.dynamic_slice_in_dim(vp, j * block_k, block_k, axis=1)
        k_pos = j * block_k + jnp.arange(block_k)
    return k_j, v_j, k_pos


def _flash_fwd_impl(q, k, v, q_offset, window, block_q, block_k):
    b, tq, kvh, g, hd = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    pq = (-tq) % block_q
    pk = (-tk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq = qp.shape[1] // block_q
    nk = kp.shape[1] // block_k
    scale = 1.0 / (hd ** 0.5)
    span = min(window + block_q, max(tk, 1)) if window else 0

    def q_block(i, _):
        q_i = lax.dynamic_slice_in_dim(qp, i * block_q, block_q, axis=1)
        q_start = q_offset + i * block_q
        q_pos = q_start + jnp.arange(block_q)

        def kv_step(carry, j):
            m, l, acc = carry
            k_j, v_j, k_pos = _kv_slice(kp, vp, q_start, j, tk, window,
                                        span, block_k)
            s = jnp.einsum("bqkgh,bskh->bkgqs", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            mask = _mask_for(q_pos, k_pos, tk, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            correction = jnp.exp(m - m_new)
            l_new = l * correction + jnp.sum(p, axis=-1)
            acc_new = acc * correction[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p.astype(v_j.dtype), v_j,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, block_q), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, block_q, hd), jnp.float32)
        n_inner = 1 if window else nk
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), jnp.arange(n_inner))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))          # [b,kv,g,bq]
        return i + 1, (out.transpose(0, 3, 1, 2, 4).astype(q.dtype), lse)

    _, (blocks, lses) = lax.scan(q_block, 0, None, length=nq)
    out = blocks.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * block_q, kvh,
                                                     g, hd)
    # lses: [nq, b, kv, g, bq] -> [b, kv, g, tq_padded]
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(b, kvh, g, nq * block_q)
    return out[:, :tq], lse


def _flash_bwd_impl(q, k, v, lse, do, q_offset, window, block_q, block_k):
    """One pass over q blocks: emit dq per block, accumulate dk/dv."""
    b, tq, kvh, g, hd = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    pq = (-tq) % block_q
    pk = (-tk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    dop = jnp.pad(do, ((0, 0), (0, pq), (0, 0), (0, 0), (0, 0)))
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, 0), (0, pq)))
    nq = qp.shape[1] // block_q
    nk = kp.shape[1] // block_k
    scale = 1.0 / (hd ** 0.5)
    span = min(window + block_q, max(tk, 1)) if window else 0
    tkp = kp.shape[1]

    # D_i = rowsum(do * o) == rowsum(do * (p @ v)); compute from p recompute:
    # standard flash keeps D = rowsum(do ⊙ o). We recompute o rows per block
    # instead of saving o: cheaper to pass do ⊙ o? We saved `out` in residuals
    # — caller passes D directly. (Here: D computed by caller.)

    def q_block(carry, i):
        dk_acc, dv_acc = carry
        q_i = lax.dynamic_slice_in_dim(qp, i * block_q, block_q, axis=1)
        do_i = lax.dynamic_slice_in_dim(dop, i * block_q, block_q, axis=1)
        lse_i = lax.dynamic_slice_in_dim(lsep, i * block_q, block_q, axis=3)
        q_start = q_offset + i * block_q
        q_pos = q_start + jnp.arange(block_q)
        # D_i = rowsum(do ⊙ o); o = (p@v) — recompute via two inner passes
        # pass 1: o_i rows (cheap re-run of fwd accumulation w/o softmax redo)

        def kv_step(carry_i, j):
            dq_i, Di = carry_i
            k_j, v_j, k_pos = _kv_slice(kp, vp, q_start, j, tk, window,
                                        span, block_k)
            s = jnp.einsum("bqkgh,bskh->bkgqs", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            mask = _mask_for(q_pos, k_pos, tk, window)
            p = jnp.exp(s - lse_i[..., None]) * mask[None, None, None]
            # dv_j += p^T do_i ; dp = do_i v_j^T
            dv_j = jnp.einsum("bkgqs,bqkgh->bskh", p.astype(do_i.dtype), do_i,
                              preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqkgh,bskh->bkgqs", do_i, v_j,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - Di[..., None]) * scale
            dq_i = dq_i + jnp.einsum("bkgqs,bskh->bqkgh",
                                     ds.astype(k_j.dtype), k_j,
                                     preferred_element_type=jnp.float32)
            dk_j = jnp.einsum("bkgqs,bqkgh->bskh", ds.astype(q_i.dtype), q_i,
                              preferred_element_type=jnp.float32)
            return (dq_i, Di), (dk_j, dv_j, k_pos[0])

        # D_i needs o rows: o = exp(s - lse) @ v summed — equivalently
        # D = rowsum(do * o). Recompute o via one extra inner scan:
        def o_step(acc, j):
            k_j, v_j, k_pos = _kv_slice(kp, vp, q_start, j, tk, window,
                                        span, block_k)
            s = jnp.einsum("bqkgh,bskh->bkgqs", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            mask = _mask_for(q_pos, k_pos, tk, window)
            p = jnp.exp(s - lse_i[..., None]) * mask[None, None, None]
            return acc + jnp.einsum("bkgqs,bskh->bqkgh",
                                    p.astype(v_j.dtype), v_j,
                                    preferred_element_type=jnp.float32), None

        n_inner = 1 if window else nk
        o_i, _ = lax.scan(o_step,
                          jnp.zeros((b, block_q, kvh, g, hd), jnp.float32),
                          jnp.arange(n_inner))
        Di = jnp.sum(do_i.astype(jnp.float32) * o_i, axis=-1)  # [b,bq,kv,g]
        Di = Di.transpose(0, 2, 3, 1)                          # [b,kv,g,bq]

        (dq_i, _), (dk_js, dv_js, starts) = lax.scan(
            kv_step,
            (jnp.zeros((b, block_q, kvh, g, hd), jnp.float32), Di),
            jnp.arange(n_inner))
        # fold dk/dv tiles back into the full buffers
        if window:
            upd_k = dk_js[0]
            upd_v = dv_js[0]
            start = starts[0]
            cur_k = lax.dynamic_slice_in_dim(dk_acc, start, span, axis=1)
            cur_v = lax.dynamic_slice_in_dim(dv_acc, start, span, axis=1)
            dk_acc = lax.dynamic_update_slice_in_dim(
                dk_acc, cur_k + upd_k, start, axis=1)
            dv_acc = lax.dynamic_update_slice_in_dim(
                dv_acc, cur_v + upd_v, start, axis=1)
        else:
            # tiles tile the whole k axis exactly once per q block
            dk_full = dk_js.transpose(1, 0, 2, 3, 4).reshape(b, tkp, kvh, hd)
            dv_full = dv_js.transpose(1, 0, 2, 3, 4).reshape(b, tkp, kvh, hd)
            dk_acc = dk_acc + dk_full
            dv_acc = dv_acc + dv_full
        return (dk_acc, dv_acc), dq_i.astype(q.dtype)

    dk0 = jnp.zeros((b, tkp, kvh, hd), jnp.float32)
    dv0 = jnp.zeros((b, tkp, kvh, hd), jnp.float32)
    (dk, dv), dq_blocks = lax.scan(q_block, (dk0, dv0), jnp.arange(nq))
    dq = dq_blocks.transpose(1, 0, 2, 3, 4, 5).reshape(
        b, nq * block_q, kvh, g, hd)[:, :tq]
    return dq, dk[:, :tk].astype(k.dtype), dv[:, :tk].astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, q_offset, window, block_q, block_k):
    out, _ = _flash_fwd_impl(q, k, v, q_offset, window, block_q, block_k)
    return out


def _flash_fwd_rule(q, k, v, q_offset, window, block_q, block_k):
    out, lse = _flash_fwd_impl(q, k, v, q_offset, window, block_q, block_k)
    return out, (q, k, v, lse)


def _flash_bwd_rule(q_offset, window, block_q, block_k, res, do):
    q, k, v, lse = res
    return _flash_bwd_impl(q, k, v, lse, do, q_offset, window,
                           block_q, block_k)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jnp.ndarray,            # [B, Tq, KV, G, hd]
    k: jnp.ndarray,            # [B, Tk, KV, hd]
    v: jnp.ndarray,            # [B, Tk, KV, hd]
    q_offset: int = 0,         # absolute position of q[0] (== Tk-Tq for causal)
    window: int = 0,           # 0 => full causal; else sliding-window
    block_q: int = 512,
    block_k: int = 1024,
) -> jnp.ndarray:
    """Blockwise causal attention, flash-style fwd AND bwd (custom VJP).

    Never materializes more than one [B, KV, G, block_q, block_k] tile in
    either direction.  With ``window`` set, each q-block statically slices
    only the k/v span it can see — sub-quadratic FLOPs for SWA archs."""
    return _flash(q, k, v, q_offset, window, block_q, block_k)


def attention_naive(q, k, v, q_offset: int = 0, window: int = 0):
    """O(T^2)-materialized oracle for tests (small shapes only)."""
    b, tq, kvh, g, hd = q.shape
    tk = k.shape[1]
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                   preferred_element_type=jnp.float32) / (hd ** 0.5)
    q_pos = q_offset + jnp.arange(tq)
    k_pos = jnp.arange(tk)
    mask = q_pos[:, None] >= k_pos[None, :]
    if window:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w.astype(v.dtype), v)
    return out


# ================================================================= RWKV6 (WKV)

def rwkv6_naive(r, k, v, w, u, state):
    """Per-step WKV6 recurrence oracle.

    r,k,w: [B,T,H,K]; v: [B,T,H,V]; u: [H,K]; state: [B,H,K,V].
    y_t = r_t · (S_{t-1} + (u ⊙ k_t) v_t^T);  S_t = diag(w_t) S_{t-1} + k_t v_t^T
    """
    def step(S, xs):
        r_t, k_t, v_t, w_t = xs
        kv = jnp.einsum("bhk,bhv->bhkv", k_t, v_t)
        y = jnp.einsum("bhk,bhkv->bhv", r_t, S + u[None, :, :, None] * kv)
        S = w_t[..., None] * S + kv
        return S, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, w))
    state, ys = lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 1), state


def rwkv6_chunked(r, k, v, w, u, state, chunk: int = 64):
    """Chunked WKV6 (the production formulation; Pallas kernel mirrors it).

    Splits T into chunks; within a chunk uses pairwise decay matrices
    (exp of log-decay differences — numerically safe since w ∈ (0,1)),
    across chunks carries the [B,H,K,V] state.
    """
    b, t, h, kdim = r.shape
    vdim = v.shape[-1]
    pad = (-t) % chunk
    if pad:
        r, k, w = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (r, k, w))
        # pad w with ones (no decay) to keep the state exact
        w = w.at[:, t:].set(1.0)
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nt = r.shape[1] // chunk

    rc = r.reshape(b, nt, chunk, h, kdim).transpose(1, 0, 3, 2, 4)
    kc = k.reshape(b, nt, chunk, h, kdim).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nt, chunk, h, vdim).transpose(1, 0, 3, 2, 4)
    wc = w.reshape(b, nt, chunk, h, kdim).transpose(1, 0, 3, 2, 4)
    # shapes now [nt, B, H, chunk, K/V]

    def chunk_step(S, xs):
        r_i, k_i, v_i, w_i = xs          # [B,H,c,K] / [B,H,c,V]
        logw = jnp.log(jnp.maximum(w_i.astype(jnp.float32), 1e-30))
        cl = jnp.cumsum(logw, axis=2)     # [B,H,c,K] inclusive
        cl_prev = cl - logw               # exclusive cumsum
        # contribution of the carried state: decayed by cl_prev at each pos
        r_f = r_i.astype(jnp.float32)
        k_f = k_i.astype(jnp.float32)
        v_f = v_i.astype(jnp.float32)
        y_state = jnp.einsum("bhck,bhkv->bhcv", r_f * jnp.exp(cl_prev), S)
        # intra-chunk: D[i,j,k] = exp(cl_prev[i] - cl[j]) for j < i
        # (k_j decays through w_{j+1..i-1})
        diff = cl_prev[:, :, :, None, :] - cl[:, :, None, :, :]  # [B,H,i,j,K]
        mask = (jnp.arange(chunk)[:, None] > jnp.arange(chunk)[None, :])
        D = jnp.exp(jnp.minimum(diff, 30.0)) * mask[None, None, :, :, None]
        att = jnp.einsum("bhik,bhijk,bhjk->bhij", r_f, D, k_f)
        # diagonal "bonus" term with u
        diag = jnp.einsum("bhik,hk,bhik->bhi", r_f, u.astype(jnp.float32), k_f)
        y_intra = jnp.einsum("bhij,bhjv->bhiv", att, v_f)
        y_diag = diag[..., None] * v_f
        y = y_state + y_intra + y_diag
        # state update: S' = exp(cl_last) ⊙ S + sum_j exp(cl_last - cl_j) k_j v_j
        cl_last = cl[:, :, -1, :]          # [B,H,K]
        S_decay = jnp.exp(cl_last)[..., None] * S
        carry_w = jnp.exp(jnp.minimum(cl_last[:, :, None, :] - cl, 30.0))
        S_new = S_decay + jnp.einsum("bhjk,bhjv->bhkv", carry_w * k_f, v_f)
        return S_new, y.astype(r.dtype)

    # remat per chunk: the backward recomputes the pairwise decay tensors
    # instead of saving [nt, B, H, c, c, K] across the whole scan
    state, ys = lax.scan(jax.checkpoint(chunk_step),
                         state.astype(jnp.float32), (rc, kc, vc, wc))
    # ys: [nt, B, H, chunk, V] -> [B, T, H, V]
    y = ys.transpose(1, 0, 3, 2, 4).reshape(b, nt * chunk, h, vdim)
    return y[:, :t], state


# ================================================================ Mamba2 (SSD)

def mamba2_naive(x, dt, A, B, C, state):
    """Per-step SSD oracle.  x: [Bt,T,H,P]; dt: [Bt,T,H]; A: [H] (negative);
    B,C: [Bt,T,N]; state: [Bt,H,P,N].
    h_t = exp(A dt_t) h_{t-1} + dt_t * x_t B_t^T ;  y_t = h_t C_t
    """
    def step(h, xs):
        x_t, dt_t, B_t, C_t = xs
        decay = jnp.exp(A * dt_t)[..., None, None]          # [Bt,H,1,1]
        upd = jnp.einsum("bhp,bn->bhpn", x_t * dt_t[..., None], B_t)
        h = decay * h + upd
        y = jnp.einsum("bhpn,bn->bhp", h, C_t)
        return h, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C))
    state, ys = lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 1), state


def mamba2_ssd(x, dt, A, B, C, state, chunk: int = 128):
    """Chunked SSD (Mamba2's matmul-friendly dual form)."""
    bt, t, h, p = x.shape
    n = B.shape[-1]
    pad = (-t) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    nt = x.shape[1] // chunk
    xc = x.reshape(bt, nt, chunk, h, p).transpose(1, 0, 3, 2, 4)   # [nt,b,h,c,p]
    dtc = dt.reshape(bt, nt, chunk, h).transpose(1, 0, 3, 2)       # [nt,b,h,c]
    Bc = B.reshape(bt, nt, chunk, n).transpose(1, 0, 2, 3)          # [nt,b,c,n]
    Cc = C.reshape(bt, nt, chunk, n).transpose(1, 0, 2, 3)

    def chunk_step(S, xs):
        x_i, dt_i, B_i, C_i = xs
        x_f = x_i.astype(jnp.float32)
        dt_f = dt_i.astype(jnp.float32)
        a = A.astype(jnp.float32)[None, :, None] * dt_f               # [b,h,c]
        cl = jnp.cumsum(a, axis=-1)
        cl_prev = cl - a
        # state contribution
        y_state = jnp.einsum("bhpn,bcn,bhc->bhcp",
                             S, C_i.astype(jnp.float32), jnp.exp(cl))
        # intra-chunk quadratic term: L[i,j] = exp(cl_i - cl_j) for j <= i
        diff = cl[:, :, :, None] - cl[:, :, None, :]
        mask = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
        L = jnp.exp(jnp.minimum(diff, 30.0)) * mask[None, None]
        G = jnp.einsum("bin,bjn->bij", C_i.astype(jnp.float32),
                       B_i.astype(jnp.float32))
        M = G[:, None] * L                                           # [b,h,i,j]
        y_intra = jnp.einsum("bhij,bhj,bhjp->bhip", M, dt_f, x_f)
        y = y_state + y_intra
        # state update
        cl_last = cl[:, :, -1]
        decay_tail = jnp.exp(jnp.minimum(cl_last[:, :, None] - cl, 30.0))
        S_new = (jnp.exp(cl_last)[..., None, None] * S
                 + jnp.einsum("bhc,bhcp,bcn->bhpn",
                              decay_tail * dt_f, x_f,
                              B_i.astype(jnp.float32)))
        return S_new, y

    # remat per chunk (see rwkv6_chunked)
    state, ys = lax.scan(jax.checkpoint(chunk_step),
                         state.astype(jnp.float32), (xc, dtc, Bc, Cc))
    # ys: [nt, b, h, c, p] -> [b, t, h, p]
    y = ys.transpose(1, 0, 3, 2, 4).reshape(bt, nt * chunk, h, p)
    return y[:, :t].astype(x.dtype), state


# ================================================================ checksum

CHECKSUM_PRIME = jnp.uint32(4_294_967_291)  # largest 32-bit prime


def checksum(data: jnp.ndarray, block: int = 4096) -> jnp.ndarray:
    """Positional-weighted modular checksum over a uint32 buffer.

    TPU-native stand-in for the extent CRC cache (paper §2.2.1): each block
    computes sum_i (i+1)*x_i and sum_i x_i in uint64-free 32-bit arithmetic
    (mod 2^32), then blocks combine associatively.  Order-sensitive like CRC,
    vectorizes on the VPU.  Returns uint32 [2] (weighted, plain).
    """
    data = data.astype(jnp.uint32)
    n = data.shape[0]
    pad = (-n) % block
    if pad:
        data = jnp.pad(data, (0, pad))
    blocks = data.reshape(-1, block)
    idx = jnp.arange(1, block + 1, dtype=jnp.uint32)
    plain = jnp.sum(blocks, axis=1, dtype=jnp.uint32)
    weighted = jnp.sum(blocks * idx[None, :], axis=1, dtype=jnp.uint32)
    nb = blocks.shape[0]
    # combine: weighted_total = sum_b (weighted_b + offset_b * plain_b)
    offsets = (jnp.arange(nb, dtype=jnp.uint32) * jnp.uint32(block))
    w_total = jnp.sum(weighted + offsets * plain, dtype=jnp.uint32)
    p_total = jnp.sum(plain, dtype=jnp.uint32)
    return jnp.stack([w_total, p_total])
