"""Mixture-of-Experts block: top-k routing with capacity + scatter dispatch.

Used by mixtral-8x22b (8e top-2) and arctic-480b (128e top-2 + dense
residual, handled by the caller).  The dispatch is the memory-lean
scatter/gather formulation:

  1. router logits -> top-k experts + renormalized weights per token,
  2. position-in-expert via a cumsum over the one-hot assignment
     ([N, E] ints — small), tokens beyond capacity C are DROPPED,
  3. scatter tokens into an [E, C, d] buffer, batched expert FFN (the only
     big matmuls — E*C*d*f FLOPs, i.e. the real active-parameter cost),
  4. gather back and combine with routing weights.

Expert-parallel sharding puts E over the "model" mesh axis when divisible
(arctic: 128/16 = 8 experts per shard); otherwise the expert hidden dim is
tensor-parallel instead (mixtral: 8e replicated, f=16384 sharded 16-way).
XLA inserts the token all-to-all at the scatter/gather boundaries.

The expert-share layer below (``moe_share``, moonlight-16b-a3b) is the
other path: one chip's share of the experts, no capacity, nothing
dropped.  Mixtral and arctic keep the capacity path above; the two share
no routing code.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ArchConfig
from .layers import Params, _dense_init, init_mlp, swiglu


def init_moe_block(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> Params:
    d, E = cfg.d_model, cfg.n_experts
    fe = cfg.d_expert or cfg.d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], d, E, jnp.float32),
        "w1": (jax.random.normal(ks[1], (E, d, fe), jnp.float32)
               * (2.0 / (d + fe)) ** 0.5).astype(dtype),
        "w3": (jax.random.normal(ks[2], (E, d, fe), jnp.float32)
               * (2.0 / (d + fe)) ** 0.5).astype(dtype),
        "w2": (jax.random.normal(ks[3], (E, fe, d), jnp.float32)
               * (2.0 / (d + fe)) ** 0.5).astype(dtype),
    }


def _maybe_constrain(x: jnp.ndarray, *axes) -> jnp.ndarray:
    """Sharding hint; no-op when no mesh context (CPU unit tests)."""
    try:
        from jax.sharding import PartitionSpec as P
        return jax.lax.with_sharding_constraint(x, P(*axes))
    except Exception:
        return x


def _route(cfg: ArchConfig, router, xg: jnp.ndarray, capacity: int):
    """Group-local routing: top-k experts + slot positions per group.
    xg [G, ng, d] -> (scatter_e, scatter_p, keep, top_w) each [G, ng*k(,)]"""
    E, k = cfg.n_experts, cfg.top_k
    G, ng, d = xg.shape
    gate_logits = jnp.einsum("gnd,de->gne", xg.astype(jnp.float32), router)
    probs = jax.nn.softmax(gate_logits, axis=-1)
    top_w, top_e = lax.top_k(probs, k)                    # [G, ng, k]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    flat_e = top_e.reshape(G, ng * k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos_in_e = jnp.cumsum(onehot, axis=1) - onehot
    flat_pos = jnp.sum(pos_in_e * onehot, axis=-1)
    keep = flat_pos < capacity
    scatter_e = jnp.where(keep, flat_e, E - 1)
    scatter_p = jnp.where(keep, flat_pos, capacity - 1)
    return scatter_e, scatter_p, keep, top_w


def moe_block_shard_map(cfg: ArchConfig, p: Params, x: jnp.ndarray,
                        mesh, mlp: Params = None) -> jnp.ndarray:
    """Expert-parallel MoE via shard_map (arctic path, E % model == 0).

    Activations are REPLICATED across the model axis between blocks, so
    every model shard routes its data-shard's tokens locally (cheap), then
    simply SLICES the [G_l, E, C, d] buffer down to its own experts —
    dispatch costs ZERO communication.  After the expert FFN, each shard
    scatter-combines only its experts' outputs and ONE psum over "model"
    completes the block (activation-sized — identical cost to a dense TP
    layer).  This replaced data-axis all-reduces of the whole buffer; see
    EXPERIMENTS.md §Perf iteration arctic#1."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    b, t, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    daxes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dp = 1
    for a in daxes:
        dp *= mesh.shape[a]
    mp = mesh.shape["model"]
    ep = E % mp == 0          # expert-parallel (arctic) vs TP-in-expert (mixtral)
    E_loc = E // mp if ep else E
    n = b * t
    G = dp
    ng = n // G
    capacity = int(ng * k / E * cfg.capacity_factor) + 1
    xg = x.reshape(G, ng, d)
    dspec = daxes if len(daxes) > 1 else daxes[0]

    def local_fn(xg_l, router, w1_l, w3_l, w2_l, *mlp_l):
        # xg_l [G_l, ng, d]; w*_l [E_loc, d, f] (EP) or [E, d, f/mp] (TP)
        G_l = xg_l.shape[0]
        scatter_e, scatter_p, keep, top_w = _route(cfg, router, xg_l,
                                                   capacity)
        src = jnp.repeat(xg_l, k, axis=1)                  # [G_l, ng*k, d]
        contrib = jnp.where(keep[..., None], src, 0)
        gidx = jnp.broadcast_to(jnp.arange(G_l)[:, None], scatter_e.shape)
        if ep:
            # my expert slice: tokens routed to experts [lo, lo+E_loc)
            lo = lax.axis_index("model") * E_loc
            mine = (scatter_e >= lo) & (scatter_e < lo + E_loc)
            e_loc = jnp.clip(scatter_e - lo, 0, E_loc - 1)
            contrib = jnp.where(mine[..., None], contrib, 0)
        else:
            # experts replicated, FFN hidden dim TP'd: every shard
            # dispatches ALL experts locally (zero comm either way)
            mine = keep
            e_loc = scatter_e
        buf = jnp.zeros((G_l, E_loc, capacity, d), x.dtype)
        buf = buf.at[gidx, e_loc, scatter_p].add(contrib, mode="drop")
        # local expert FFN (partial over f when TP)
        gate = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, w1_l))
        up = jnp.einsum("gecd,edf->gecf", buf, w3_l)
        out_buf = jnp.einsum("gecf,efd->gecd", gate * up, w2_l)
        # combine contributing outputs back to token order
        gathered = out_buf[gidx, e_loc, scatter_p]
        gathered = jnp.where((mine & keep)[..., None], gathered, 0)
        w = top_w.reshape(G_l, ng * k, 1).astype(x.dtype)
        out = jnp.sum((gathered * w).reshape(G_l, ng, k, d), axis=2)
        if mlp_l:
            # arctic's dense-residual MLP, TP-partial, folded into the SAME
            # psum as the expert combine (saves one all-reduce per layer)
            m1, m3, m2 = mlp_l
            gate_d = jax.nn.silu(jnp.einsum("gnd,df->gnf", xg_l, m1))
            up_d = jnp.einsum("gnd,df->gnf", xg_l, m3)
            out = out + jnp.einsum("gnf,fd->gnd", gate_d * up_d, m2)
        return lax.psum(out, "model")

    w_specs = ((P("model", None, None),) * 2 + (P("model", None, None),)
               if ep else
               (P(None, None, "model"), P(None, None, "model"),
                P(None, "model", None)))
    mlp_args = (mlp["w1"], mlp["w3"], mlp["w2"]) if mlp is not None else ()
    mlp_specs = (P(None, "model"), P(None, "model"),
                 P("model", None)) if mlp is not None else ()
    out = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(dspec, None, None), P(), *w_specs, *mlp_specs),
        out_specs=P(dspec, None, None),
        check_vma=False,
    )(xg, p["router"], p["w1"], p["w3"], p["w2"], *mlp_args)
    return out.reshape(b, t, d)


def moe_block(cfg: ArchConfig, p: Params, x: jnp.ndarray,
              groups: int = 16, mlp: Params = None) -> jnp.ndarray:
    """x [B, T, d] -> [B, T, d].

    GROUP-LOCAL dispatch (GShard/MaxText style): tokens are split into
    ``groups`` groups aligned with the data shards; capacity and the
    scatter positions are computed PER GROUP, so the [G, E, C_g, d] buffer
    is sharded over data on G and over model on E — the dispatch becomes
    one all-to-all of buffer bytes instead of data-axis all-reduces of the
    whole buffer (the §Perf hillclimb fix; see EXPERIMENTS.md)."""
    b, t, d = x.shape
    E, k = cfg.n_experts, cfg.top_k

    # production path: expert-parallel shard_map when the mesh is known and
    # experts divide the model axis (arctic: 128/16)
    from ..parallel import ctx
    mesh = ctx.get_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        dp = 1
        for a in mesh.axis_names:
            if a in ("pod", "data"):
                dp *= mesh.shape[a]
        if (b * t) % dp == 0 and (b * t) >= dp:
            return moe_block_shard_map(cfg, p, x, mesh, mlp=mlp)
        # tiny token counts (batch-1 long-context decode) can't form
        # per-data-shard groups: take the local dispatch below

    n = b * t
    G = groups
    while n % G or (n // G) < 1:      # tiny smoke-test shapes
        G //= 2
    ng = n // G
    xg = x.reshape(G, ng, d)
    xg = _maybe_constrain(xg, "data", None, None)

    capacity = int(ng * k / E * cfg.capacity_factor) + 1
    scatter_e, scatter_p, keep, top_w = _route(cfg, p["router"], xg, capacity)

    # scatter tokens into [G, E, C, d]
    buf = jnp.zeros((G, E, capacity, d), x.dtype)
    src = jnp.repeat(xg, k, axis=1)                       # [G, ng*k, d]
    contrib = jnp.where(keep[..., None], src, 0)
    gidx = jnp.broadcast_to(jnp.arange(G)[:, None], scatter_e.shape)
    buf = buf.at[gidx, scatter_e, scatter_p].add(contrib, mode="drop")
    buf = _maybe_constrain(buf, "data", None, None, None)

    # batched expert FFN (SwiGLU) — E sharded over model, G over data
    gate = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["w1"]))
    up = jnp.einsum("gecd,edf->gecf", buf, p["w3"])
    out_buf = jnp.einsum("gecf,efd->gecd", gate * up, p["w2"])
    out_buf = _maybe_constrain(out_buf, "data", "model", None, None)

    # gather back + combine
    gathered = out_buf[gidx, scatter_e, scatter_p]        # [G, ng*k, d]
    gathered = jnp.where(keep[..., None], gathered, 0)
    w = top_w.reshape(G, ng * k, 1).astype(x.dtype)
    out = jnp.sum((gathered * w).reshape(G, ng, k, d), axis=2).reshape(b, t, d)
    if mlp is not None:
        out = out + swiglu(mlp, x)
    return out


# ------------------------------------------------------------ expert share
#
# One chip's share of an expert-parallel layer (DeepSeek-V3 style): the
# router keeps its published width and experts per token, the chip holds
# experts [expert_lo, expert_lo + n_experts_held) and computes their part
# of the result for every token routed to them, without a capacity: none
# is dropped.  What the absent experts would add is left out, as it lies
# on other chips; the shared experts are always added.  No exchange runs.


def init_moe_share(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> Params:
    d, E, held = cfg.d_model, cfg.n_experts, cfg.n_experts_held
    fe = cfg.d_expert or cfg.d_ff
    ks = jax.random.split(key, 5)
    scale = (2.0 / (d + fe)) ** 0.5

    def experts(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    p = {"router": _dense_init(ks[0], d, E, dtype),
         "w1": experts(ks[1], (held, d, fe)),
         "w3": experts(ks[2], (held, d, fe)),
         "w2": experts(ks[3], (held, fe, d))}
    if cfg.router == "sigmoid":
        p["router_bias"] = jnp.zeros((E,), dtype)
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(d, cfg.n_shared_experts * fe, ks[4], dtype)
    return p


def route_topk(cfg: ArchConfig, p: Params, x: jnp.ndarray):
    """x [N, d] -> (experts [N, k] int32, weights [N, k] float32), over all
    ``n_experts``.  Scores are float32 throughout: softmax, or sigmoid
    with top-k taken of score + correction bias (the weights are the
    scores without it); the chosen weights are normalised and scaled by
    ``routed_scale``."""
    logits = jnp.dot(x.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores + p["router_bias"].astype(jnp.float32)
    else:
        scores = choice = jax.nn.softmax(logits, axis=-1)
    _, top_e = lax.top_k(choice, cfg.top_k)
    top_w = jnp.take_along_axis(scores, top_e, axis=-1)
    top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
    return top_e, top_w * cfg.routed_scale


def held_experts(cfg: ArchConfig, p: Params, x: jnp.ndarray,
                 top_e: jnp.ndarray, top_w: jnp.ndarray):
    """The held experts' part of the routed result, x [N, d] -> [N, d],
    and (rows computed, the busiest held expert's rows) as int32 [2].

    The N*k assignments are sorted by held expert, those to absent experts
    last; one ragged product per weight runs over a buffer of N*min(k,
    held) rows, the most the held experts can be given, so none is
    dropped; rows past the held groups are not read."""
    n, k = top_e.shape
    held, lo = cfg.n_experts_held, cfg.expert_lo
    local = top_e.reshape(-1) - lo
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held)
    order = jnp.argsort(group, stable=True)
    rows = n * min(k, held)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
    xs = jnp.take(x, order[:rows] // k, axis=0)
    h = (jax.nn.silu(lax.ragged_dot(xs, p["w1"], sizes))
         * lax.ragged_dot(xs, p["w3"], sizes))
    ys = lax.ragged_dot(h, p["w2"], sizes)
    # back to assignment order; an absent expert's assignment reads zero,
    # never a row past the held groups
    where = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
    back = jnp.take(ys, jnp.where(mine, where, rows), axis=0, mode="fill",
                    fill_value=0)
    w = jnp.where(mine, top_w.reshape(-1), 0.0).reshape(n, k)
    out = jnp.einsum("nkd,nk->nd", back.reshape(n, k, -1), w,
                     preferred_element_type=jnp.float32)
    counts = jnp.stack([jnp.sum(sizes), jnp.max(sizes)])
    return out.astype(x.dtype), counts


def moe_share(cfg: ArchConfig, p: Params, x: jnp.ndarray):
    """x [B, T, d] (normed) -> (held experts' part + shared experts
    [B, T, d], int32 [2] rows computed and busiest held expert's rows)."""
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    with jax.named_scope("moe.route"):
        top_e, top_w = route_topk(cfg, p, xf)
    with jax.named_scope("moe.experts"):
        out, counts = held_experts(cfg, p, xf, top_e, top_w)
    out = out.reshape(b, t, d)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            out = out + swiglu(p["shared"], x)
    return out, counts
