"""jit'd public entry points for the kernels.

Dispatch policy: on TPU the Pallas kernels run compiled; everywhere else
they run in interpret mode (tests) while the MODELS lower through the
``ref`` implementations (same math, same memory shape), so the models'
programs keep the production structure and the kernels stay validated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .checksum import checksum as _checksum_pallas
from .flash_attention import flash_attention_fwd as _flash_pallas
from .mamba2_ssd import ssd_fwd as _ssd_pallas
from .rwkv6_scan import wkv6_fwd as _wkv6_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                             "use_pallas"))
def flash_attention(q, k, v, window: int = 0, block_q: int = 128,
                    block_k: int = 128, use_pallas: bool = False):
    """Causal (optionally sliding-window) attention.
    q [B,Tq,KV,G,hd]; k/v [B,Tk,KV,hd]."""
    if use_pallas or _on_tpu():
        return _flash_pallas(q, k, v, window=window, block_q=block_q,
                             block_k=block_k, interpret=not _on_tpu())
    return ref.flash_attention(q, k, v, window=window,
                               block_q=block_q, block_k=block_k)


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas"))
def wkv6(r, k, v, w, u, chunk: int = 64, use_pallas: bool = False):
    """RWKV6 recurrence from zero state -> y [B,T,H,V]."""
    if use_pallas or _on_tpu():
        return _wkv6_pallas(r, k, v, w, u, chunk=chunk,
                            interpret=not _on_tpu())
    b, _, h, kd = r.shape
    vd = v.shape[-1]
    s0 = jnp.zeros((b, h, kd, vd), jnp.float32)
    y, _ = ref.rwkv6_chunked(r, k, v, w, u, s0, chunk=chunk)
    return y


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas"))
def mamba2_ssd(x, dt, A, B, C, chunk: int = 128, use_pallas: bool = False):
    """Mamba2 SSD scan from zero state -> y [Bt,T,H,P]."""
    if use_pallas or _on_tpu():
        return _ssd_pallas(x, dt, A, B, C, chunk=chunk,
                           interpret=not _on_tpu())
    bt, _, h, p = x.shape
    n = B.shape[-1]
    s0 = jnp.zeros((bt, h, p, n), jnp.float32)
    y, _ = ref.mamba2_ssd(x, dt, A, B, C, s0, chunk=chunk)
    return y


@functools.partial(jax.jit, static_argnames=("block", "use_pallas"))
def tensor_checksum(data, block: int = 4096, use_pallas: bool = False):
    """Device-side integrity digest of a uint32 view of a tensor."""
    if use_pallas or _on_tpu():
        return _checksum_pallas(data, block=block, interpret=not _on_tpu())
    return ref.checksum(data, block=block)
