"""Pallas kernels (interpret mode) vs ref.py oracles — shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.checksum import checksum as checksum_pallas
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.mamba2_ssd import ssd_fwd
from repro.kernels.rwkv6_scan import wkv6_fwd


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("t,window,dtype", [
    (128, 0, jnp.float32), (256, 0, jnp.float32), (96, 0, jnp.float32),
    (128, 32, jnp.float32), (128, 0, jnp.bfloat16),
])
@pytest.mark.parametrize("kv,g", [(2, 1), (2, 2)])
def test_flash_pallas_sweep(t, window, dtype, kv, g):
    key = jax.random.PRNGKey(0)
    b, hd = 2, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, t, kv, g, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (b, t, kv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, kv, hd)).astype(dtype)
    out = flash_attention_fwd(q, k, v, window=window, block_q=64, block_k=64,
                              interpret=True)
    oracle = ref.attention_naive(q.astype(jnp.float32),
                                 k.astype(jnp.float32),
                                 v.astype(jnp.float32), window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle, np.float32),
                               rtol=tol, atol=tol)


# ----------------------------------------------------------------- wkv6
@pytest.mark.parametrize("t,chunk", [(64, 16), (128, 32), (100, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_pallas_sweep(t, chunk, dtype):
    key = jax.random.PRNGKey(1)
    b, h, kd, vd = 2, 2, 16, 16
    ks = jax.random.split(key, 6)
    r = (jax.random.normal(ks[0], (b, t, h, kd)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (b, t, h, kd)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (b, t, h, vd)) * 0.5).astype(dtype)
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, kd)) - 1.0
                       ).astype(jnp.float32)
    u = (jax.random.normal(ks[4], (h, kd)) * 0.3).astype(jnp.float32)
    y = wkv6_fwd(r.astype(jnp.float32), k.astype(jnp.float32),
                 v.astype(jnp.float32), w, u, chunk=chunk,
                 interpret=True)
    s0 = jnp.zeros((b, h, kd, vd), jnp.float32)
    oracle, _ = ref.rwkv6_naive(r.astype(jnp.float32),
                                k.astype(jnp.float32),
                                v.astype(jnp.float32), w, u, s0)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-3
    np.testing.assert_allclose(np.asarray(y), np.asarray(oracle),
                               rtol=tol, atol=tol)


# ----------------------------------------------------------------- mamba2 ssd
@pytest.mark.parametrize("t,chunk", [(64, 16), (128, 64), (100, 32)])
def test_ssd_pallas_sweep(t, chunk):
    key = jax.random.PRNGKey(2)
    bt, h, p, n = 2, 3, 16, 8
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (bt, t, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bt, t, h)) - 1.0)
    A = -jnp.abs(jax.random.normal(ks[2], (h,)))
    B = jax.random.normal(ks[3], (bt, t, n)) * 0.5
    C = jax.random.normal(ks[4], (bt, t, n)) * 0.5
    y = ssd_fwd(x, dt, A, B, C, chunk=chunk, interpret=True)
    s0 = jnp.zeros((bt, h, p, n), jnp.float32)
    oracle, _ = ref.mamba2_naive(x, dt, A, B, C, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(oracle),
                               rtol=3e-3, atol=3e-3)


# ----------------------------------------------------------------- checksum
@pytest.mark.parametrize("n,block", [(1000, 256), (4096, 4096), (10000, 512)])
def test_checksum_pallas_matches_ref(n, block):
    data = jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(2654435761)
    got = checksum_pallas(data, block=block, interpret=True)
    want = ref.checksum(data, block=4096)   # block must not matter
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_checksum_pallas_detects_bitflip():
    data = jnp.arange(5000, dtype=jnp.uint32)
    c0 = checksum_pallas(data, block=1024, interpret=True)
    c1 = checksum_pallas(data.at[777].set(42), block=1024,
                         interpret=True)
    assert not np.array_equal(np.asarray(c0), np.asarray(c1))


def test_ops_dispatch():
    from repro.kernels import ops
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 64, 2, 1, 32))
    k = jax.random.normal(key, (1, 64, 2, 32))
    v = jax.random.normal(key, (1, 64, 2, 32))
    a = ops.flash_attention(q, k, v)                     # ref path
    b = ops.flash_attention(q, k, v, use_pallas=True)    # pallas interpret
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-3, atol=2e-3)
