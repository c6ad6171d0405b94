"""The jit-able training step: loss -> grad -> clip -> AdamW.

Microbatching (gradient accumulation) happens OUTSIDE via the batch shape;
remat inside the model keeps activations O(1) in depth.  The same function
runs on the chip (``chip_smoke.py``) and in the CPU examples.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..models import get_model
from . import optimizer as opt


def make_train_step(cfg: ArchConfig, oc: opt.OptConfig):
    api = get_model(cfg)

    def train_step(params, opt_state: opt.OptState,
                   batch: Dict[str, jnp.ndarray]):
        loss, grads = jax.value_and_grad(api.loss)(params, batch)
        grads, gnorm = opt.clip_by_global_norm(grads, oc.clip_norm)
        new_params, new_state = opt.adamw_update(oc, params, grads, opt_state)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "lr": opt.schedule(oc, new_state.step)}
        return new_params, new_state, metrics

    return train_step


def make_eval_step(cfg: ArchConfig):
    api = get_model(cfg)

    def eval_step(params, batch):
        return api.loss(params, batch)

    return eval_step
