"""Multi-device equivalence tests for the §Perf distribution machinery.

Runs in a SUBPROCESS with 8 fake host devices (XLA_FLAGS must be set before
jax imports, and the main test process must keep seeing 1 device), and
checks that the optimized paths are numerically IDENTICAL to the mesh-free
reference paths:

  * shard_map MoE dispatch (EP and TP-in-expert variants) == local dispatch
  * TP head padding == unpadded attention
"""

import os
import subprocess
import sys

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P, NamedSharding

from repro.configs import get_arch
from repro.models.moe import moe_block, init_moe_block
from repro.models import transformer, get_model
from repro.parallel import ctx, sharding as shd
import dataclasses

assert len(jax.devices()) == 8, jax.devices()
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)

# ---------- MoE: shard_map vs local (EP variant: E=4 divides model=4) ----
cfg = dataclasses.replace(get_arch("arctic-480b").reduced(),
                          n_experts=4, top_k=2, capacity_factor=4.0)
key = jax.random.PRNGKey(0)
p = init_moe_block(cfg, key, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)

ctx.set_mesh(None)
ref = moe_block(cfg, p, x)
ctx.set_mesh(mesh)
with mesh:
    got = jax.jit(lambda p, x: moe_block(cfg, p, x))(p, x)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                           rtol=2e-4, atol=2e-4)
print("moe EP shard_map == local: OK")

# ---------- MoE TP-in-expert variant: E=3 does NOT divide model=4 --------
cfg2 = dataclasses.replace(cfg, n_experts=3, top_k=2)
p2 = init_moe_block(cfg2, jax.random.PRNGKey(2), jnp.float32)
ctx.set_mesh(None)
ref2 = moe_block(cfg2, p2, x)
ctx.set_mesh(mesh)
with mesh:
    got2 = jax.jit(lambda p, x: moe_block(cfg2, p, x))(p2, x)
np.testing.assert_allclose(np.asarray(got2), np.asarray(ref2),
                           rtol=2e-4, atol=2e-4)
print("moe TP shard_map == local: OK")

# ---------- TP head padding: H=6 over model=4 -> Hp=8, exact -------------
cfg3 = dataclasses.replace(get_arch("qwen1.5-32b").reduced(),
                           n_heads=6, n_kv_heads=6, head_dim=16, n_layers=1)
api = get_model(cfg3)
params = api.init(jax.random.PRNGKey(3), jnp.float32)
toks = jax.random.randint(jax.random.PRNGKey(4), (2, 16), 0, cfg3.vocab)
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}

ctx.set_mesh(None)
loss_ref = float(api.loss(params, batch))
ctx.set_mesh(mesh)
with mesh:
    loss_pad = float(jax.jit(api.loss)(params, batch))
assert abs(loss_ref - loss_pad) < 1e-4, (loss_ref, loss_pad)
print("head padding exact: OK", loss_ref, loss_pad)

# ---------- GQA-uneven expansion: H=6, KV=2 over model=4 ------------------
cfg4 = dataclasses.replace(get_arch("phi3-medium-14b").reduced(),
                           n_heads=6, n_kv_heads=2, head_dim=16, n_layers=1)
api4 = get_model(cfg4)
params4 = api4.init(jax.random.PRNGKey(5), jnp.float32)
toks4 = jax.random.randint(jax.random.PRNGKey(6), (2, 16), 0, cfg4.vocab)
batch4 = {"tokens": toks4, "labels": jnp.roll(toks4, -1, 1)}
ctx.set_mesh(None)
l_ref = float(api4.loss(params4, batch4))
ctx.set_mesh(mesh)
with mesh:
    l_pad = float(jax.jit(api4.loss)(params4, batch4))
assert abs(l_ref - l_pad) < 1e-4, (l_ref, l_pad)
print("GQA kv expansion exact: OK")

# ---------- train_step executes under shardings on the real 8-dev mesh ----
from repro.train import optimizer as opt
from repro.train.train_step import make_train_step
oc = opt.opt_config_for(cfg3, lr=1e-3)
step = make_train_step(cfg3, oc)
params_sh = jax.device_put(params, shd.param_shardings(cfg3, params, mesh))
opt_state = opt.init_opt_state(oc, params_sh)
with mesh:
    ctx.set_mesh(mesh)
    p2_, o2_, m_ = jax.jit(step)(params_sh, opt_state, batch)
assert np.isfinite(float(m_["loss"]))
print("sharded train_step executes: OK, loss", float(m_["loss"]))
"""


def test_multidevice_equivalence():
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, "-c", _SCRIPT],
        capture_output=True, text=True, timeout=900,
        # JAX_PLATFORMS=cpu: the child must never claim an accelerator
        env={"PYTHONPATH": "src", "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "HOME": os.path.expanduser("~"), "JAX_PLATFORMS": "cpu"},
        cwd=repo_root,
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr[-3000:]}"
    assert "sharded train_step executes: OK" in res.stdout
