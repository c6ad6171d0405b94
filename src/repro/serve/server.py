"""Batched serving with continuous slot reuse.

A fixed pool of ``batch`` sequence slots; finished sequences are replaced by
queued requests (prefill into the free slot's cache region is approximated
by re-prefilling the whole batch only when a slot JOINS — for the CPU
example this keeps the code simple while exercising prefill+decode+KV
reuse).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..configs.base import ArchConfig
from ..models import get_model


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int = 16
    out: Optional[List[int]] = None


class BatchServer:
    def __init__(self, cfg: ArchConfig, params, batch: int = 4,
                 smax: int = 128, temperature: float = 0.0):
        self.cfg = cfg
        self.api = get_model(cfg)
        self.params = params
        self.batch = batch
        self.smax = smax
        self._prefill = jax.jit(
            lambda p, t: self.api.prefill(p, t, smax, "bfloat16", False))
        self._decode = jax.jit(self.api.decode)

    def serve(self, requests: List[Request]) -> List[Request]:
        """Serve a queue of requests through fixed batch slots."""
        queue = list(requests)
        done: List[Request] = []
        while queue:
            wave = queue[: self.batch]
            queue = queue[self.batch :]
            with obs.span("server.wave", slots=len(wave)) as sp:
                # pad the wave to full batch with a dummy
                while len(wave) < self.batch:
                    wave.append(Request(rid=-1, prompt=[0], max_new=0))
                max_p = max(len(r.prompt) for r in wave)
                toks = np.zeros((self.batch, max_p), np.int32)
                for i, r in enumerate(wave):
                    toks[i, max_p - len(r.prompt):] = r.prompt  # left-pad
                logits, cache = self._prefill(self.params, jnp.asarray(toks))
                busiest = _count_experts(sp, cache, 0)
                cur = jnp.argmax(logits[:, -1, : self.cfg.vocab], -1).astype(
                    jnp.int32)
                outs = [[int(cur[i])] for i in range(self.batch)]
                cache_len = jnp.int32(max_p)
                steps = max((r.max_new for r in wave), default=0)
                for _ in range(max(steps - 1, 0)):
                    logits, cache = self._decode(self.params, cur[:, None],
                                                 cache, cache_len)
                    cache_len = cache_len + 1
                    busiest = _count_experts(sp, cache, busiest)
                    cur = jnp.argmax(logits[:, -1, : self.cfg.vocab],
                                     -1).astype(jnp.int32)
                    for i in range(self.batch):
                        outs[i].append(int(cur[i]))
                if busiest is not None:
                    sp.add(expert_rows_max=busiest)
                for i, r in enumerate(wave):
                    if r.rid >= 0:
                        r.out = outs[i][: r.max_new]
                        done.append(r)
                        sp.add(tokens=len(r.out))
        return done


def _count_experts(sp, cache, busiest: Optional[int]) -> Optional[int]:
    """Adds the expert-share layer's rows of one prefill or decode step
    (``cache["expert_rows"]``: rows computed, busiest expert's rows) to the
    wave's span; returns the busiest expert's rows so far, or None for a
    model without that layer."""
    if "expert_rows" not in cache:
        return None
    rows, most = (int(v) for v in np.asarray(cache["expert_rows"]))
    sp.add(expert_rows=rows)
    return max(busiest or 0, most)
