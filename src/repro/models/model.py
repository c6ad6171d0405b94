"""Uniform model API over every assigned architecture.

    api = get_model(cfg)
    params = api.init(key, dtype)
    loss   = api.loss(params, {"tokens", "labels"})
    logits, cache = api.prefill(params, tokens, smax, kv_dtype)
    logits, cache = api.decode(params, token, cache, cache_len)
    cache_specs   = api.cache_spec(batch, smax, kv_dtype)   # ShapeDtypeStructs

musicgen-large and chameleon-34b reuse the dense-transformer backbone:
their modality frontends are stubs, and the backbone takes token ids.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import rwkv6, transformer, zamba2
from .layers import Params


@dataclasses.dataclass(frozen=True)
class ModelApi:
    cfg: ArchConfig
    init: Callable[..., Params]
    loss: Callable[..., jnp.ndarray]
    prefill: Callable[..., Any]
    decode: Callable[..., Any]
    cache_spec: Callable[..., Dict[str, jax.ShapeDtypeStruct]]


def _sds(spec: Dict[str, Any]) -> Dict[str, jax.ShapeDtypeStruct]:
    return {k: jax.ShapeDtypeStruct(shape, dt) for k, (shape, dt) in spec.items()}


def get_model(cfg: ArchConfig) -> ModelApi:
    if cfg.family == "ssm":          # rwkv6
        return ModelApi(
            cfg=cfg,
            init=lambda key, dtype=jnp.bfloat16: rwkv6.init_params(cfg, key, dtype),
            loss=lambda p, b: rwkv6.loss_fn(cfg, p, b),
            prefill=lambda p, toks, smax, kv="bfloat16", remat=True:
                rwkv6.prefill(cfg, p, toks, smax, kv, remat),
            decode=lambda p, tok, cache, cache_len:
                rwkv6.decode_step(cfg, p, tok, cache, cache_len),
            cache_spec=lambda batch, smax, kv="bfloat16":
                _sds(rwkv6.state_spec(cfg, batch)),
        )
    if cfg.family == "hybrid":       # zamba2
        return ModelApi(
            cfg=cfg,
            init=lambda key, dtype=jnp.bfloat16: zamba2.init_params(cfg, key, dtype),
            loss=lambda p, b: zamba2.loss_fn(cfg, p, b),
            prefill=lambda p, toks, smax, kv="bfloat16", remat=True:
                zamba2.prefill(cfg, p, toks, smax, kv, remat),
            decode=lambda p, tok, cache, cache_len:
                zamba2.decode_step(cfg, p, tok, cache, cache_len),
            cache_spec=lambda batch, smax, kv="bfloat16":
                _sds(zamba2.state_spec(cfg, batch, smax, kv)),
        )
    # dense / moe / audio / vlm all use the transformer backbone
    return ModelApi(
        cfg=cfg,
        init=lambda key, dtype=jnp.bfloat16: transformer.init_params(cfg, key, dtype),
        loss=lambda p, b: transformer.loss_fn(cfg, p, b),
        prefill=lambda p, toks, smax, kv="bfloat16", remat=True:
            transformer.prefill(cfg, p, toks, smax, kv, remat),
        decode=lambda p, tok, cache, cache_len:
            transformer.decode_step(cfg, p, tok, cache, cache_len),
        cache_spec=lambda batch, smax, kv="bfloat16":
            _sds(transformer.kv_cache_spec(cfg, batch, smax, kv)),
    )

