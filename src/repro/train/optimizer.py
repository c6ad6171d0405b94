"""Optimizer: AdamW with configurable moment dtype + LR schedules.

* moments in fp32 by default; the huge MoE archs keep bf16 moments and
  no fp32 master copy to fit HBM (config flags);
* optional fp32 master weights (disabled for arctic);
* WSD (warmup-stable-decay) schedule for minicpm, cosine for the rest;
* global-norm gradient clipping.

Implemented from scratch (no optax dependency) as flat pytree transforms so
the ZeRO-1 output shardings apply leaf-by-leaf.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    betas: Tuple[float, float] = (0.9, 0.95)
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    schedule: str = "cosine"         # "cosine" | "wsd" | "const"
    stable_frac: float = 0.8         # WSD: fraction of steps at peak LR
    moment_dtype: Any = jnp.float32
    master_weights: bool = False


def opt_config_for(cfg: ArchConfig, **overrides) -> OptConfig:
    base = OptConfig(
        schedule="wsd" if cfg.lr_schedule == "wsd" else "cosine",
        moment_dtype=(jnp.bfloat16 if cfg.optimizer_moment_dtype == "bfloat16"
                      else jnp.float32),
        master_weights=cfg.use_master_weights and
                       cfg.optimizer_moment_dtype == "float32",
    )
    return dataclasses.replace(base, **overrides)


class OptState(NamedTuple):
    step: jnp.ndarray          # scalar int32
    mu: Any                    # first moments (pytree like params)
    nu: Any                    # second moments
    master: Any                # fp32 master weights or None-tree


def schedule(oc: OptConfig, step: jnp.ndarray) -> jnp.ndarray:
    s = step.astype(jnp.float32)
    warm = jnp.minimum(s / jnp.maximum(oc.warmup_steps, 1), 1.0)
    if oc.schedule == "const":
        return oc.lr * warm
    total = float(oc.total_steps)
    if oc.schedule == "wsd":
        # warmup -> stable plateau -> inverse-exponential decay tail
        stable_end = total * oc.stable_frac
        in_decay = jnp.clip((s - stable_end) / jnp.maximum(
            total - stable_end, 1.0), 0.0, 1.0)
        decay = 0.5 ** (in_decay * 10.0)      # ~1000x down over the tail
        return oc.lr * warm * decay
    # cosine
    frac = jnp.clip(s / total, 0.0, 1.0)
    return oc.lr * warm * (0.1 + 0.45 * (1 + jnp.cos(jnp.pi * frac)))


def init_opt_state(oc: OptConfig, params: Any) -> OptState:
    zeros = lambda p: jnp.zeros(p.shape, oc.moment_dtype)
    mu = jax.tree.map(zeros, params)
    nu = jax.tree.map(zeros, params)
    # a copy even for fp32 params: the train step donates params and
    # optimizer state, and one buffer cannot be donated twice
    master = (jax.tree.map(lambda p: jnp.array(p, jnp.float32, copy=True),
                           params)
              if oc.master_weights else None)
    return OptState(jnp.zeros((), jnp.int32), mu, nu, master)


def clip_by_global_norm(grads: Any, max_norm: float):
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in jax.tree.leaves(grads))
    gnorm = jnp.sqrt(sq)
    scale = jnp.minimum(1.0, max_norm / (gnorm + 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale
                                   ).astype(g.dtype), grads), gnorm


def _decay_mask(path) -> bool:
    """No weight decay on norms / biases / 1-D params."""
    leaf_name = str(path[-1].key) if hasattr(path[-1], "key") else ""
    return not (leaf_name.startswith("ln") or leaf_name.startswith("b")
                or "norm" in leaf_name)


def adamw_update(oc: OptConfig, params: Any, grads: Any, state: OptState
                 ) -> Tuple[Any, OptState]:
    step = state.step + 1
    lr = schedule(oc, step)
    b1, b2 = oc.betas
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(path, p, g, mu, nu, master):
        g32 = g.astype(jnp.float32)
        mu_n = b1 * mu.astype(jnp.float32) + (1 - b1) * g32
        nu_n = b2 * nu.astype(jnp.float32) + (1 - b2) * g32 * g32
        update = (mu_n / c1) / (jnp.sqrt(nu_n / c2) + oc.eps)
        base = master if master is not None else p
        base32 = base.astype(jnp.float32)
        if _decay_mask(path):
            update = update + oc.weight_decay * base32
        new32 = base32 - lr * update
        new_p = new32.astype(p.dtype)
        new_master = new32 if master is not None else None
        return new_p, mu_n.astype(oc.moment_dtype), \
            nu_n.astype(oc.moment_dtype), new_master

    paths_leaves = jax.tree_util.tree_flatten_with_path(params)
    paths = [pl[0] for pl in paths_leaves[0]]
    p_leaves = [pl[1] for pl in paths_leaves[0]]
    treedef = paths_leaves[1]
    g_leaves = treedef.flatten_up_to(grads)
    mu_leaves = treedef.flatten_up_to(state.mu)
    nu_leaves = treedef.flatten_up_to(state.nu)
    ms_leaves = (treedef.flatten_up_to(state.master)
                 if state.master is not None else [None] * len(p_leaves))

    outs = [upd(pt, p, g, m, n, ms) for pt, p, g, m, n, ms
            in zip(paths, p_leaves, g_leaves, mu_leaves, nu_leaves, ms_leaves)]
    new_params = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
    new_mu = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
    new_nu = jax.tree_util.tree_unflatten(treedef, [o[2] for o in outs])
    new_master = (jax.tree_util.tree_unflatten(treedef, [o[3] for o in outs])
                  if state.master is not None else None)
    return new_params, OptState(step, new_mu, new_nu, new_master)
