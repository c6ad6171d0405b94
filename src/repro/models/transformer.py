"""Decoder-only LM (dense + MoE variants) with scan-over-layers + remat.

Covers: codeqwen1.5-7b, phi3-medium-14b, minicpm-2b, qwen1.5-32b,
musicgen-large (audio backbone), chameleon-34b (vlm backbone),
mixtral-8x22b and arctic-480b (MoE block via models.moe),
moonlight-16b-a3b (latent attention via models.mla, leading dense layers,
and the expert-share MoE layer), and kimi-linear-48b-a3b (KDA layers via
models.kda beside NoPE latent attention; see "hybrid" below).

Layer parameters are stacked on a leading [L] axis and consumed by
``lax.scan`` with ``jax.checkpoint`` — HLO stays one-layer-sized and
activation memory stays O(1) in depth.  Leading dense layers of an MoE
model (``first_k_dense``) are a stack of their own, ``params["dense"]``,
scanned before ``params["layers"]``; the cache stacks every layer.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ArchConfig
from ..kernels import ref
from . import layers
from .kda import init_kda, kda_decode, kda_prefill
from .layers import Params
from .mla import init_mla, mla_decode, mla_prefill
from .moe import init_moe_block, init_moe_share, moe_block, moe_share


def _residual_scale(cfg: ArchConfig) -> float:
    # minicpm: depth-scaled residual branch (scale_depth / sqrt(L))
    return 1.4 / (cfg.n_layers ** 0.5) if cfg.depth_scaled_residual else 1.0


# ------------------------------------------------------------------ init

def init_layer(cfg: ArchConfig, key, dtype=jnp.bfloat16,
               dense: bool = False, kind: str = "attn") -> Params:
    """One layer's weights; ``dense``: a leading dense layer of an MoE
    model; ``kind``: its attention, "attn" or "kda"."""
    if cfg.family != "moe" and not cfg.kv_lora_rank:
        return layers.init_block(cfg, key, dtype)
    k1, k2 = jax.random.split(key)
    if kind == "kda":
        attn = init_kda(cfg, k1, dtype)
    elif cfg.kv_lora_rank:
        attn = init_mla(cfg, k1, dtype)
    else:
        attn = layers.init_attention(cfg, k1, dtype)
    p = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": attn,
        "ln2": jnp.ones((cfg.d_model,), dtype),
    }
    if dense or cfg.family != "moe":
        p["mlp"] = layers.init_mlp(cfg.d_model, cfg.d_ff, k2, dtype)
    elif cfg.n_experts_held:
        p["moe"] = init_moe_share(cfg, k2, dtype)
    else:
        p["moe"] = init_moe_block(cfg, k2, dtype)
        if cfg.dense_residual:
            p["mlp"] = layers.init_mlp(cfg.d_model, cfg.d_ff,
                                       jax.random.fold_in(k2, 7), dtype)
    return p


def init_params(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> Params:
    k_emb, k_layers = jax.random.split(key)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    n_dense = cfg.first_k_dense
    if cfg.kda_layers:
        return _init_hybrid(cfg, k_emb, layer_keys, dtype)
    if n_dense and not cfg.kv_lora_rank:
        raise NotImplementedError("leading dense layers are served with "
                                  "latent attention only")
    p = {"emb": layers.init_embeddings(cfg, k_emb, dtype),
         "layers": jax.vmap(lambda k: init_layer(cfg, k, dtype))(
             layer_keys[n_dense:])}
    if n_dense:
        p["dense"] = jax.vmap(lambda k: init_layer(cfg, k, dtype, True))(
            layer_keys[:n_dense])
    return p


def _stacks(params: Params):
    """The scanned layer stacks in order: leading dense layers, if any,
    then the rest."""
    return ([params["dense"]] if "dense" in params else []) + [
        params["layers"]]


# ------------------------------------------------------------------ forward

def _mix(cfg: ArchConfig, lp: Params, h: jnp.ndarray):
    """The FFN/MoE half of a block: (output, the expert-share layer's int32
    [2] rows computed and busiest expert's rows, else None)."""
    hin = layers.rms_norm(h, lp["ln2"], cfg.norm_eps)
    if "moe" not in lp:
        return layers.swiglu(lp["mlp"], hin), None
    if cfg.n_experts_held:
        return moe_share(cfg, lp["moe"], hin)
    # the dense-residual branch (arctic) is fused into the MoE combine
    # psum when the shard_map path is active
    return moe_block(cfg, lp["moe"], hin,
                     mlp=lp.get("mlp") if cfg.dense_residual else None), None


def _expert_rows(counts: jnp.ndarray) -> jnp.ndarray:
    """Per-layer [L, 2] counts -> int32 [2]: rows summed over the layers,
    the busiest expert's rows in any layer."""
    return jnp.stack([jnp.sum(counts[:, 0]), jnp.max(counts[:, 1])])


def _attn_full(cfg: ArchConfig, lp: Params, h: jnp.ndarray,
               positions: jnp.ndarray) -> jnp.ndarray:
    x = layers.rms_norm(h, lp["ln1"], cfg.norm_eps)
    if cfg.kv_lora_rank:
        return mla_prefill(cfg, lp["attn"], x, positions)[0]
    q, k, v = layers._qkv(cfg, lp["attn"], x, positions, pad_tp=True)
    hp, kvh = q.shape[2], k.shape[2]
    g = hp // kvh
    out = ref.flash_attention(q.reshape(*q.shape[:2], kvh, g, cfg.hd),
                              k, v, window=cfg.swa_window)
    out = out.reshape(*out.shape[:2], hp * cfg.hd)
    wo = lp["attn"]["wo"]
    if hp != cfg.n_heads:   # zero rows for the phantom heads (exact)
        wo = jnp.pad(wo, ((0, (hp - cfg.n_heads) * cfg.hd), (0, 0)))
    return jnp.einsum("bth,hd->btd", out, wo)


def forward(cfg: ArchConfig, params: Params, tokens: jnp.ndarray,
            remat: bool = True) -> jnp.ndarray:
    """tokens [B, T] -> logits [B, T, V]."""
    if cfg.kda_layers:
        h, _ = _hybrid_prefill_layers(cfg, params, tokens, tokens.shape[1],
                                      remat)
        return layers.unembed(params["emb"], h, cfg.norm_eps)
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    h = layers.embed(params["emb"], tokens)
    rs = _residual_scale(cfg)

    def block(h, lp):
        h = h + rs * _attn_full(cfg, lp, h, positions)
        h = h + rs * _mix(cfg, lp, h)[0]
        return h, None

    block_fn = jax.checkpoint(block) if remat else block
    for stack in _stacks(params):
        h, _ = lax.scan(block_fn, h, stack)
    return layers.unembed(params["emb"], h, cfg.norm_eps)


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, jnp.ndarray]
            ) -> jnp.ndarray:
    logits = forward(cfg, params, batch["tokens"])
    return layers.cross_entropy(logits, batch["labels"], cfg.vocab)


# ------------------------------------------------------------------ serving

def kv_cache_spec(cfg: ArchConfig, batch: int, smax: int, dtype_name: str):
    """Shapes of the per-layer-stacked KV cache."""
    kvh, hd, L = cfg.n_kv_heads, cfg.hd, cfg.n_layers
    if cfg.kda_layers:
        return _hybrid_cache_spec(cfg, batch, smax, dtype_name)
    if cfg.kv_lora_rank:
        if dtype_name != "bfloat16":
            raise NotImplementedError("the latent cache is bfloat16 only")
        spec = {"ckv": ((L, batch, smax,
                         cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                        jnp.bfloat16)}
        if cfg.n_experts_held:
            spec["expert_rows"] = ((2,), jnp.int32)
        return spec
    if cfg.swa_window:
        smax = min(smax, cfg.swa_window)    # SWA: ring buffer of window size
    if dtype_name == "int8":
        return {
            "k": ((L, batch, smax, kvh, hd), jnp.int8),
            "v": ((L, batch, smax, kvh, hd), jnp.int8),
            "k_scale": ((L, batch, smax, kvh, 1), jnp.bfloat16),
            "v_scale": ((L, batch, smax, kvh, 1), jnp.bfloat16),
        }
    return {
        "k": ((L, batch, smax, kvh, hd), jnp.bfloat16),
        "v": ((L, batch, smax, kvh, hd), jnp.bfloat16),
    }


def _latent_prefill(cfg: ArchConfig, params: Params, tokens: jnp.ndarray,
                    smax: int, remat: bool):
    """``prefill`` of a latent-attention model: the cache is ``ckv``
    [L, B, smax, rank + rope] and, with the expert-share layer,
    ``expert_rows``."""
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    h = layers.embed(params["emb"], tokens)
    rs = _residual_scale(cfg)

    def block(h, lp):
        a, entry = mla_prefill(cfg, lp["attn"],
                               layers.rms_norm(h, lp["ln1"], cfg.norm_eps),
                               positions)
        h = h + rs * a
        m, counts = _mix(cfg, lp, h)
        entry = jnp.pad(entry.astype(jnp.bfloat16),
                        ((0, 0), (0, smax - t), (0, 0)))
        return h + rs * m, (entry, counts)

    block_fn = jax.checkpoint(block) if remat else block
    entries, counts = [], []
    for stack in _stacks(params):
        h, (e, c) = lax.scan(block_fn, h, stack)
        entries.append(e)
        if c is not None:
            counts.append(c)
    cache = {"ckv": jnp.concatenate(entries)}
    if counts:
        cache["expert_rows"] = _expert_rows(jnp.concatenate(counts))
    return layers.unembed(params["emb"], h[:, -1:], cfg.norm_eps), cache


def prefill(cfg: ArchConfig, params: Params, tokens: jnp.ndarray,
            smax: int, kv_dtype_name: str = "bfloat16", remat: bool = True):
    """Process the full prompt; return (last-token logits, cache dict)."""
    if cfg.kda_layers:
        h, cache = _hybrid_prefill_layers(cfg, params, tokens, smax, remat)
        return layers.unembed(params["emb"], h[:, -1:], cfg.norm_eps), cache
    if cfg.kv_lora_rank:
        return _latent_prefill(cfg, params, tokens, smax, remat)
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    h = layers.embed(params["emb"], tokens)
    rs = _residual_scale(cfg)
    kv_dtype = jnp.int8 if kv_dtype_name == "int8" else jnp.bfloat16
    cache_smax = min(smax, cfg.swa_window) if cfg.swa_window else smax

    def block(h, lp):
        hin = layers.rms_norm(h, lp["ln1"], cfg.norm_eps)
        q, k, v = layers._qkv(cfg, lp["attn"], hin, positions)
        kvh, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
        out = ref.flash_attention(q.reshape(*q.shape[:2], kvh, g, cfg.hd),
                                  k, v, window=cfg.swa_window)
        out = out.reshape(b, t, cfg.n_heads * cfg.hd)
        h = h + rs * jnp.einsum("bth,hd->btd", out, lp["attn"]["wo"])
        h = h + rs * _mix(cfg, lp, h)[0]
        # cache tail: last cache_smax positions (= all for full attention)
        k_tail = k[:, -cache_smax:] if cfg.swa_window else k
        v_tail = v[:, -cache_smax:] if cfg.swa_window else v
        pad = cache_smax - k_tail.shape[1]
        k_tail = jnp.pad(k_tail, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_tail = jnp.pad(v_tail, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_dtype == jnp.int8:
            kq, ks = layers._quantize_kv(k_tail)
            vq, vs = layers._quantize_kv(v_tail)
            return h, {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        return h, {"k": k_tail.astype(kv_dtype), "v": v_tail.astype(kv_dtype)}

    block_fn = jax.checkpoint(block) if remat else block
    h, cache = lax.scan(block_fn, h, params["layers"])
    logits = layers.unembed(params["emb"], h[:, -1:], cfg.norm_eps)
    return logits, cache


def _latent_decode(cfg: ArchConfig, params: Params, token: jnp.ndarray,
                   cache: Dict[str, jnp.ndarray], cache_len: jnp.ndarray):
    """``decode_step`` of a latent-attention model."""
    h = layers.embed(params["emb"], token)
    rs = _residual_scale(cfg)

    def block(h, xs):
        lp = xs["layer"]
        a, ckv = mla_decode(cfg, lp["attn"],
                            layers.rms_norm(h, lp["ln1"], cfg.norm_eps),
                            xs["ckv"], cache_len)
        h = h + rs * a
        m, counts = _mix(cfg, lp, h)
        return h + rs * m, (ckv, counts)

    ckvs, counts, first = [], [], 0
    for stack in _stacks(params):
        n = jax.tree.leaves(stack)[0].shape[0]
        h, (c, n_rows) = lax.scan(
            block, h, {"layer": stack, "ckv": cache["ckv"][first:first + n]})
        ckvs.append(c)
        if n_rows is not None:
            counts.append(n_rows)
        first += n
    new = {"ckv": jnp.concatenate(ckvs)}
    if counts:
        new["expert_rows"] = _expert_rows(jnp.concatenate(counts))
    return layers.unembed(params["emb"], h, cfg.norm_eps), new


def decode_step(cfg: ArchConfig, params: Params, token: jnp.ndarray,
                cache: Dict[str, jnp.ndarray], cache_len: jnp.ndarray):
    """One decode step.  token [B,1]; cache from ``prefill``/``kv_cache_spec``;
    cache_len: scalar int32.  Returns (logits [B,1,V], new cache)."""
    if cfg.kda_layers:
        return _hybrid_decode(cfg, params, token, cache, cache_len)
    if cfg.kv_lora_rank:
        return _latent_decode(cfg, params, token, cache, cache_len)
    b = token.shape[0]
    h = layers.embed(params["emb"], token)
    rs = _residual_scale(cfg)
    int8 = "k_scale" in cache
    smax = cache["k"].shape[2]
    if cfg.swa_window:
        write_pos = cache_len % smax        # ring buffer wraps the window
    else:
        write_pos = cache_len
    n_valid = jnp.minimum(cache_len + 1, smax)

    def block(h, xs):
        lp = xs["layer"]
        scales = (xs["k_scale"], xs["v_scale"]) if int8 else None
        out, ck, cv, sc = layers.attention_decode(
            cfg, lp["attn"], layers.rms_norm(h, lp["ln1"], cfg.norm_eps),
            xs["k"], xs["v"], write_pos, cache_len, n_valid, kv_scale=scales)
        h = h + rs * out
        h = h + rs * _mix(cfg, lp, h)[0]
        new = {"k": ck, "v": cv}
        if int8:
            new["k_scale"], new["v_scale"] = sc
        return h, new

    xs = {"layer": params["layers"], "k": cache["k"], "v": cache["v"]}
    if int8:
        xs["k_scale"], xs["v_scale"] = cache["k_scale"], cache["v_scale"]
    h, new_cache = lax.scan(block, h, xs)
    logits = layers.unembed(params["emb"], h, cfg.norm_eps)
    return logits, new_cache


# ------------------------------------------------------- hybrid (KDA + MLA)
#
# A model with KDA layers (kimi-linear-48b-a3b) holds its layers in their
# published order: the leading dense layers (KDA and a SwiGLU) as
# ``params["dense"]``, then the MoE layers of each kind stacked,
# ``params["kda"]`` and ``params["mla"]``.  The MoE layers run period by
# period, a period being a run of KDA layers and then a run of latent ones;
# one scan runs the periods of each shape (Kimi Linear's first 20 layers:
# one period of 2 KDA + 1 MLA after the dense layer, then 4 of 3 + 1), so
# the program holds one body per shape of period, not one per layer.  The
# cache holds both kinds of state side by side: ``kda_state`` [L_kda, B, H,
# K, K] float32 and ``kda_conv`` [L_kda, B, conv - 1, 3 H K] over the KDA
# layers in order, ``ckv`` over the latent ones.


def _periods(cfg: ArchConfig) -> List[Tuple[int, int, int]]:
    """The MoE layers as (periods, KDA layers, latent layers) groups, in
    order, each group a run of periods of one shape."""
    kinds = cfg.layer_kinds()[cfg.first_k_dense:]
    runs, i = [], 0
    while i < len(kinds):
        a = m = 0
        while i < len(kinds) and kinds[i] == "kda":
            a, i = a + 1, i + 1
        while i < len(kinds) and kinds[i] == "attn":
            m, i = m + 1, i + 1
        runs.append((a, m))
    groups: List[Tuple[int, int, int]] = []
    for a, m in runs:
        if groups and groups[-1][1:] == (a, m):
            groups[-1] = (groups[-1][0] + 1, a, m)
        else:
            groups.append((1, a, m))
    return groups


def _init_hybrid(cfg: ArchConfig, k_emb, layer_keys, dtype) -> Params:
    kinds, n_dense = cfg.layer_kinds(), cfg.first_k_dense
    if any(k != "kda" for k in kinds[:n_dense]):
        raise NotImplementedError("a KDA model's leading dense layers are "
                                  "KDA layers")
    p = {"emb": layers.init_embeddings(cfg, k_emb, dtype)}
    stacks = {"dense": (list(range(n_dense)), "kda", True),
              "kda": ([i for i in range(n_dense, cfg.n_layers)
                       if kinds[i] == "kda"], "kda", False),
              "mla": ([i for i in range(n_dense, cfg.n_layers)
                       if kinds[i] == "attn"], "attn", False)}
    for name, (idx, kind, dense) in stacks.items():
        if idx:
            p[name] = jax.vmap(lambda k: init_layer(
                cfg, k, dtype, dense, kind))(layer_keys[jnp.asarray(idx)])
    return p


def _hybrid_cache_spec(cfg: ArchConfig, batch: int, smax: int,
                       dtype_name: str):
    if dtype_name != "bfloat16":
        raise NotImplementedError("the latent cache is bfloat16 only")
    n_kda = cfg.layer_kinds().count("kda")
    H, K = cfg.kda_heads, cfg.kda_head_dim
    spec = {"kda_state": ((n_kda, batch, H, K, K), jnp.float32),
            "kda_conv": ((n_kda, batch, cfg.kda_conv - 1, 3 * H * K),
                         jnp.bfloat16),
            "ckv": ((cfg.n_layers - n_kda, batch, smax,
                     cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                    jnp.bfloat16)}
    if cfg.n_experts_held:
        spec["expert_rows"] = ((2,), jnp.int32)
    return spec


def _hybrid_layers(cfg: ArchConfig, params: Params, h: jnp.ndarray,
                   kda_fn, mla_fn, cache: Optional[Dict[str, jnp.ndarray]],
                   remat: bool):
    """Every layer in order over h.  ``kda_fn(p, x, state, tail)`` ->
    (out, state, tail) and ``mla_fn(p, x, ckv)`` -> (out, ckv) are the
    attention of prefill (``cache`` None: no state in) or decode (each
    layer's entries of ``cache`` in).  Returns (h, the new cache)."""
    rs = _residual_scale(cfg)

    def ffn(h, lp, a):
        h = h + rs * a
        m, counts = _mix(cfg, lp, h)
        return h + rs * m, counts

    def kda_block(h, xs):
        lp = xs["layer"]
        a, state, tail = kda_fn(lp["attn"], layers.rms_norm(
            h, lp["ln1"], cfg.norm_eps), xs["state"], xs["tail"])
        h, counts = ffn(h, lp, a)
        return h, {"kda_state": state, "kda_conv": tail.astype(jnp.bfloat16),
                   "rows": counts}

    def mla_block(h, xs):
        lp = xs["layer"]
        a, ckv = mla_fn(lp["attn"], layers.rms_norm(h, lp["ln1"],
                                                    cfg.norm_eps), xs["ckv"])
        h, counts = ffn(h, lp, a)
        return h, {"ckv": ckv, "rows": counts}

    if remat:
        kda_block, mla_block = jax.checkpoint(kda_block), jax.checkpoint(
            mla_block)

    def entries(name, lo, n):
        return None if cache is None else cache[name][lo:lo + n]

    def take(stack, lo, n):
        return jax.tree.map(lambda x: x[lo:lo + n], stack)

    def fold(tree, n):              # [n * a, ...] -> [n, a, ...]
        return jax.tree.map(lambda x: x.reshape(n, -1, *x.shape[1:]), tree)

    outs = []
    n_dense = cfg.first_k_dense
    if n_dense:
        h, o = lax.scan(kda_block, h, {
            "layer": params["dense"], "state": entries("kda_state", 0, n_dense),
            "tail": entries("kda_conv", 0, n_dense)})
        outs.append(o)
    nk = nm = 0
    for n, a, m in _periods(cfg):
        xs = {}
        if a:
            xs["kda"] = fold({
                "layer": take(params["kda"], nk, n * a),
                "state": entries("kda_state", n_dense + nk, n * a),
                "tail": entries("kda_conv", n_dense + nk, n * a)}, n)
        if m:
            xs["mla"] = fold({"layer": take(params["mla"], nm, n * m),
                              "ckv": entries("ckv", nm, n * m)}, n)

        def period(h, xs, a=a, m=m):
            o = {}
            if a:
                h, o["kda"] = lax.scan(kda_block, h, xs["kda"])
            if m:
                h, o["mla"] = lax.scan(mla_block, h, xs["mla"])
            return h, o

        h, o = lax.scan(period, h, xs)
        for kind in ("kda", "mla"):
            if kind in o:
                outs.append(jax.tree.map(
                    lambda x: x.reshape(-1, *x.shape[2:]), o[kind]))
        nk, nm = nk + n * a, nm + n * m

    def joined(name):
        return [o[name] for o in outs if name in o]

    new = {name: jnp.concatenate(joined(name))
           for name in ("kda_state", "kda_conv", "ckv") if joined(name)}
    rows = [r for r in joined("rows") if r is not None]
    if rows:
        new["expert_rows"] = _expert_rows(jnp.concatenate(rows))
    return h, new


def _hybrid_prefill_layers(cfg: ArchConfig, params: Params,
                           tokens: jnp.ndarray, smax: int, remat: bool):
    """The prompt through every layer: (h [B, T, d], the cache)."""
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))

    def kda_fn(p, x, state, tail):
        return kda_prefill(cfg, p, x)

    def mla_fn(p, x, ckv):
        out, entry = mla_prefill(cfg, p, x, positions)
        return out, jnp.pad(entry.astype(jnp.bfloat16),
                            ((0, 0), (0, smax - t), (0, 0)))

    return _hybrid_layers(cfg, params, layers.embed(params["emb"], tokens),
                          kda_fn, mla_fn, None, remat)


def _hybrid_decode(cfg: ArchConfig, params: Params, token: jnp.ndarray,
                   cache: Dict[str, jnp.ndarray], cache_len: jnp.ndarray):
    """``decode_step`` of a hybrid model."""
    def kda_fn(p, x, state, tail):
        return kda_decode(cfg, p, x, state, tail)

    def mla_fn(p, x, ckv):
        return mla_decode(cfg, p, x, ckv, cache_len)

    h, new = _hybrid_layers(cfg, params, layers.embed(params["emb"], token),
                            kda_fn, mla_fn, cache, remat=False)
    return layers.unembed(params["emb"], h, cfg.norm_eps), new
