"""Pallas TPU kernel: extent-integrity checksum (paper §2.2.1, C1).

CFS caches a CRC per extent to verify data integrity cheaply.  CRC32's
bit-serial polynomial division has no MXU/VPU analogue, so the TPU-native
adaptation is a positional-weighted modular checksum: per VMEM tile the VPU
computes Σxᵢ and Σ(i+1)·xᵢ in 32-bit arithmetic (mod 2³²); tiles combine
ASSOCIATIVELY (weighted_total = Σ_b weighted_b + offset_b · plain_b), so any
tiling gives the same digest — order-sensitive like CRC, fully vectorized,
one pass over HBM.

Layout: the buffer is viewed lane-dense as [rows, 128]; each grid step
reads a [tile_rows, 128] tile and writes one [2, 128] block of per-lane
partial sums (weighted, plain), which the wrapper folds.  Arithmetic is in
int32 (two's-complement add/mul wrap exactly like uint32).

Used device-side to fingerprint tensor shards at checkpoint save/load; the
storage plane keeps bit-exact CRC32 (zlib) for its on-disk extents.

Oracle: ``ref.checksum``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8


def _checksum_kernel(x_ref, out_ref, *, tile_rows: int):
    x = x_ref[...]                                          # [rows, 128] i32
    shape = (tile_rows, LANES)
    idx = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, shape, 1) + 1)
    weighted = jnp.sum(x * idx, axis=0, keepdims=True)      # [1, 128]
    plain = jnp.sum(x, axis=0, keepdims=True)
    out_ref[0] = jnp.concatenate([weighted, plain], axis=0)


def checksum(data: jnp.ndarray, block: int = 4096, *,
             interpret: bool) -> jnp.ndarray:
    """uint32 buffer -> uint32[2] digest (weighted, plain).

    ``block`` is the tile size in words; it is rounded up to whole
    [8, 128] tiles, which leaves the digest unchanged."""
    data = data.astype(jnp.uint32).reshape(-1)
    n = data.shape[0]
    tile = SUBLANES * LANES
    block = max(tile, -(-min(block, max(n, 1)) // tile) * tile)
    pad = (-n) % block
    if pad:
        data = jnp.pad(data, (0, pad))
    nb = data.shape[0] // block
    tile_rows = block // LANES
    x = jax.lax.bitcast_convert_type(data, jnp.int32).reshape(-1, LANES)

    kernel = functools.partial(_checksum_kernel, tile_rows=tile_rows)
    partial = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((tile_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, 2, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 2, LANES), jnp.int32),
        interpret=interpret,
    )(x)
    per_block = jax.lax.bitcast_convert_type(partial, jnp.uint32).sum(
        axis=2, dtype=jnp.uint32)                           # [nb, 2]
    # associative combine (same formula as the ref oracle)
    offsets = jnp.arange(nb, dtype=jnp.uint32) * jnp.uint32(block)
    weighted = jnp.sum(per_block[:, 0] + offsets * per_block[:, 1],
                       dtype=jnp.uint32)
    plain = jnp.sum(per_block[:, 1], dtype=jnp.uint32)
    return jnp.stack([weighted, plain])
