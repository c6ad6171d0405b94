"""Compiles for a described TPU v5e chip, with no chip attached.

The four Pallas kernels at the widths of the layers they serve, the
train step ``chip_smoke.py`` runs (minicpm-2b at published widths, depth
cut) and the benchmark's Moonlight serving prefill, whose memory must fit
one chip.  What the chip's compiler refuses
here costs no chip time.  Nothing runs, so nothing here is a timing.

The topology is described only inside the module fixture: libtpu may be
loaded by one process at a time, so describing it while a module is
imported would make the test workers collect different tests.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.checksum import checksum
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.mamba2_ssd import ssd_fwd
from repro.kernels.rwkv6_scan import wkv6_fwd
from repro.launch.train import arch_config
from repro.models import get_model
from repro.train import optimizer as opt
from repro.train.trainer import jit_train_step

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # the compiler writes no logs
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _kernel_case(name, sds):
    """(jitted kernel, argument shapes) at the real widths."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    t = 1024
    if name == "flash_attention":
        cfg = get_arch("minicpm-2b")          # 36 heads x 64, MHA
        kvh, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
        fn = lambda q, k, v: flash_attention_fwd(  # noqa: E731
            q, k, v, interpret=False)
        return fn, [sds((2, t, kvh, g, cfg.hd), bf16),
                    sds((2, t, kvh, cfg.hd), bf16),
                    sds((2, t, kvh, cfg.hd), bf16)]
    if name == "wkv6":
        cfg = get_arch("rwkv6-1.6b")          # 32 heads x 64
        h, kd = cfg.d_model // cfg.ssm_head_dim, cfg.ssm_head_dim
        fn = lambda r, k, v, w, u: wkv6_fwd(  # noqa: E731
            r, k, v, w, u, chunk=64, interpret=False)
        return fn, [sds((2, t, h, kd), f32)] * 4 + [sds((h, kd), f32)]
    if name == "mamba2_ssd":
        cfg = get_arch("zamba2-7b")           # 112 heads, P=64, N=64
        p = cfg.ssm_head_dim
        h, n = cfg.ssm_expand * cfg.d_model // p, cfg.ssm_state
        fn = lambda x, dt, a, b, c: ssd_fwd(  # noqa: E731
            x, dt, a, b, c, chunk=128, interpret=False)
        return fn, [sds((2, t, h, p), f32), sds((2, t, h), f32),
                    sds((h,), f32), sds((2, t, n), f32), sds((2, t, n), f32)]
    assert name == "checksum"
    fn = lambda d: checksum(d, interpret=False)  # noqa: E731
    return fn, [sds((1 << 24,), jnp.uint32)]


@pytest.mark.parametrize("name", ["flash_attention", "wkv6", "mamba2_ssd",
                                  "checksum"])
def test_kernel_compiles_for_v5e(one_chip, name):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    fn, args = _kernel_case(name, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_smoke_train_step_fits_one_v5e(one_chip, smoke):
    cfg = arch_config(smoke.ARCH, smoke.LAYERS)
    oc = opt.opt_config_for(cfg, lr=1e-3, warmup_steps=5,
                            total_steps=smoke.STEPS)
    on_chip = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        s.shape, s.dtype, sharding=one_chip)
    api = get_model(cfg)
    params = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0),
                                             jnp.float32))
    state = jax.eval_shape(lambda p: opt.init_opt_state(oc, p), params)
    tokens = jax.ShapeDtypeStruct((smoke.BATCH, smoke.SEQ), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    args = jax.tree.map(on_chip, (params, state, batch))
    compiled = jit_train_step(cfg, oc).lower(*args).compile()
    mem = compiled.memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves((params, state)))
    # donation: the new params and optimizer state reuse the old buffers
    assert mem.alias_size_in_bytes >= state_bytes
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert peak < V5E_HBM_BYTES, f"train step needs {peak} bytes"


def test_moonlight_prefill_fits_one_v5e(one_chip):
    """The serving prefill of the benchmarked Moonlight share (21 layers,
    8 of 64 experts held, bf16) for a wave of 32 prompts of 1,024 tokens,
    as ``BatchServer`` jits it: the weights, the latent cache and the
    prefill's temporaries fit one chip's 16 GiB."""
    import dataclasses
    from repro.serve.server import BatchServer
    cfg = dataclasses.replace(get_arch("moonlight-16b-a3b"), n_layers=21,
                              n_experts_held=8)
    on_chip = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
        s.shape, s.dtype, sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: get_model(cfg).init(jax.random.PRNGKey(0), jnp.bfloat16)))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 2 * 2_762_180_352
    srv = BatchServer(cfg, None, batch=32, smax=1025)
    tokens = on_chip(jax.ShapeDtypeStruct((32, 1024), jnp.int32))
    mem = srv._prefill.lower(params, tokens).compile().memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert peak < V5E_HBM_BYTES, f"prefill needs {peak} bytes"
