"""Trainer: the end-to-end loop wiring models + optimizer + CFS storage.

Fault-tolerance contract (tested):
  * checkpoint every ``ckpt_every`` steps through ``CheckpointManager``
    (crash-safe commit order, CRC-verified restore);
  * ``Trainer.resume()`` restores params/opt-state/step from the volume and
    REPLAYS the exact data order (deterministic ``ShardReader.batch_at``),
    so crash+resume reproduces the uninterrupted run bit-for-bit (on CPU);
  * data reads are hedged (straggler mitigation);
  * elastic restart: a checkpoint written under one topology restores under
    another (shard-count change), then re-shards at device_put.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..models import get_model
from ..storage.checkpoint import CheckpointManager, InjectedCrash
from ..storage.datapipe import ShardReader
from . import optimizer as opt
from .train_step import make_train_step


def jit_train_step(cfg: ArchConfig, oc: opt.OptConfig):
    """The jitted train step.  Params and optimizer state are donated: the
    step's outputs reuse their buffers, so one copy of the training state is
    on the device."""
    return jax.jit(make_train_step(cfg, oc), donate_argnums=(0, 1))


@dataclasses.dataclass
class TrainerConfig:
    ckpt_every: int = 5
    ckpt_base: str = "/ckpt"
    log_every: int = 1
    max_steps: int = 100
    micro_batches: int = 1        # gradient accumulation factor


class Trainer:
    def __init__(self, cfg: ArchConfig, oc: opt.OptConfig, tc: TrainerConfig,
                 mount, reader: ShardReader, seed: int = 0,
                 param_dtype=jnp.float32):
        self.cfg = cfg
        self.oc = oc
        self.tc = tc
        self.reader = reader
        self.api = get_model(cfg)
        self.ckpt = CheckpointManager(mount, tc.ckpt_base, shards=2)
        self.step_fn = jit_train_step(cfg, oc)
        key = jax.random.PRNGKey(seed)
        self.params = self.api.init(key, param_dtype)
        self.opt_state = opt.init_opt_state(oc, self.params)
        self.step = 0
        self.history: list = []

    # ---- persistence ---------------------------------------------------------
    def state_tree(self) -> Dict[str, Any]:
        return {"params": self.params,
                "mu": self.opt_state.mu, "nu": self.opt_state.nu,
                "master": self.opt_state.master,
                "step": jnp.asarray(self.opt_state.step)}

    def save(self, crash_after: Optional[int] = None) -> None:
        self.ckpt.save(self.step, self.state_tree(), crash_after=crash_after)

    def resume(self) -> bool:
        """Replace the state with the latest checkpoint's; False if there is
        none.  The current state is dropped first and each leaf goes to the
        device as it is read, so neither the device nor the host holds two
        copies of the state (a failed restore leaves the trainer without
        one)."""
        latest = self.ckpt.latest_step()
        if latest is None:
            return False
        shapes = jax.eval_shape(self.state_tree)
        self.params = self.opt_state = None
        restored, step = self.ckpt.restore(shapes, put=jnp.asarray)
        self.params = restored["params"]
        self.opt_state = opt.OptState(step=restored["step"],
                                      mu=restored["mu"], nu=restored["nu"],
                                      master=restored["master"])
        self.step = step
        return True

    # ---- loop ------------------------------------------------------------------
    def train(self, n_steps: Optional[int] = None,
              crash_at: Optional[int] = None) -> list:
        n = n_steps if n_steps is not None else self.tc.max_steps
        target = self.step + n
        while self.step < target:
            batch = self.reader.batch_at(self.step)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            self.params, self.opt_state, metrics = self.step_fn(
                self.params, self.opt_state, batch)
            self.step += 1
            if self.step % self.tc.log_every == 0:
                self.history.append(
                    {"step": self.step,
                     "loss": float(metrics["loss"]),
                     "grad_norm": float(metrics["grad_norm"])})
            if crash_at is not None and self.step == crash_at:
                raise InjectedCrash(
                    f"injected trainer crash at step {self.step}")
            if self.step % self.tc.ckpt_every == 0:
                self.save()
        return self.history
