#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python chipbench/rehearse.py [--batch 2]

Prints each program's ``memory_analysis()``: the train step of
``minicpm-2b-l2-train`` at its batch x 4096, the prefill of
``minicpm-2b-serve`` at (8, 1024) over 40 bf16 layers, and the jitted leaf
fingerprint at the largest served leaf.  Nothing runs, so nothing here is
a timing.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from chipbench import common  # noqa: E402


def _mem(name: str, compiled) -> None:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(f"{name}: arguments={m.argument_size_in_bytes} "
          f"outputs={m.output_size_in_bytes} aliased={m.alias_size_in_bytes} "
          f"temporaries={m.temp_size_in_bytes} total={total} "
          f"({total / 2**30:.2f} GiB)", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=None,
                    help="train batch (default: the workload's)")
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=chip), tree)

    from repro.models import get_model
    from repro.train import optimizer as opt
    from repro.train.trainer import jit_train_step

    # train step, as Trainer builds it
    wl = common.load_workload("minicpm2b-train-stream")
    cfg = common.arch_config(wl["config_spec"])
    tp = wl["traffic_params"]
    batch = args.batch or tp["batch"]
    seq = tp["seq"]
    oc = common.opt_config(cfg, wl["config_spec"])
    api = get_model(cfg)
    params = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0),
                                             jnp.float32))
    state = jax.eval_shape(lambda p: opt.init_opt_state(oc, p), params)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    step = jit_train_step(cfg, oc)
    compiled = step.lower(on_chip(params), on_chip(state),
                          on_chip({"tokens": tokens, "labels": tokens})
                          ).compile()
    _mem(f"train step {cfg.name} L={cfg.n_layers} batch={batch}x{seq}",
         compiled)

    # prefill, as BatchServer.__init__ builds it
    wl = common.load_workload("minicpm2b-cold-start")
    cfg = common.arch_config(wl["config_spec"])
    tp = wl["traffic_params"]
    api = get_model(cfg)
    params = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0),
                                             jnp.bfloat16))
    smax = tp["prompt_len"] + tp["max_new"]
    prefill = jax.jit(lambda p, t: api.prefill(p, t, smax, "bfloat16", False))
    toks = jax.ShapeDtypeStruct((tp["slots"], tp["prompt_len"]), jnp.int32)
    compiled = prefill.lower(on_chip(params), on_chip(toks)).compile()
    _mem(f"prefill {cfg.name} L={cfg.n_layers} wave={tp['slots']}x"
         f"{tp['prompt_len']} smax={smax}", compiled)

    from chipbench.reference import dense
    dm = dense.Dims.of(wl["config_spec"])
    gen = jax.jit(dense.serve_weights, static_argnums=(0, 1),
                  out_shardings=chip)
    _mem("serve weights from the seed (bf16)", gen.lower(dm, 0).compile())

    largest = max(jax.tree.leaves(params), key=lambda s: s.size)
    compiled = jax.jit(common.fingerprint).lower(on_chip(largest)).compile()
    _mem(f"fingerprint {largest.dtype}{tuple(largest.shape)}", compiled)


if __name__ == "__main__":
    main()
