"""Plain reference of the dense decoder that the configurations run.

Straight ``jax.numpy`` from the configuration file's sizes; it imports
nothing of the program.  It follows the program's model (see the
file's ``departures``): RMSNorm, RoPE on half-split heads, causal softmax
attention, SwiGLU, residual branches scaled by ``scale_depth /
sqrt(layers)``, a tied head, and the mean cross-entropy over ids below
``vocab_size``.  Attention and the loss are taken over blocks of rows so
that the reference fits beside nothing else on one chip; the blocks change
the order of no sum that matters.

``dtype`` is the precision everything runs in: float32 at "highest"
matmul precision is the reference, and a lower one is the control.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG = -1e30


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    hd: int
    ff: int
    layers: int
    vocab: int
    vocab_rows: int
    theta: float
    eps: float
    res_scale: float

    @classmethod
    def of(cls, spec: Dict[str, Any]) -> "Dims":
        pad = spec["vocab_padded_to"]
        layers = spec["num_hidden_layers"]
        return cls(d=spec["hidden_size"], heads=spec["num_attention_heads"],
                   kv_heads=spec["num_key_value_heads"], hd=spec["head_dim"],
                   ff=spec["intermediate_size"], layers=layers,
                   vocab=spec["vocab_size"],
                   vocab_rows=(spec["vocab_size"] + pad - 1) // pad * pad,
                   theta=spec["rope_theta"], eps=spec["rms_norm_eps"],
                   res_scale=spec["scale_depth"] / math.sqrt(layers))


# ----------------------------------------------------------------- weights

def _dense(key, fan_in: int, fan_out: int):
    return (jax.random.normal(key, (fan_in, fan_out), jnp.float32)
            * (2.0 / (fan_in + fan_out)) ** 0.5)


def _layer(dm: Dims, key) -> Dict[str, Any]:
    k_attn, k_mlp = jax.random.split(key)
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    return {"ln1": jnp.ones((dm.d,), jnp.float32),
            "attn": {"wq": _dense(ka[0], dm.d, dm.heads * dm.hd),
                     "wk": _dense(ka[1], dm.d, dm.kv_heads * dm.hd),
                     "wv": _dense(ka[2], dm.d, dm.kv_heads * dm.hd),
                     "wo": _dense(ka[3], dm.heads * dm.hd, dm.d)},
            "ln2": jnp.ones((dm.d,), jnp.float32),
            "mlp": {"w1": _dense(km[0], dm.d, dm.ff),
                    "w3": _dense(km[1], dm.d, dm.ff),
                    "w2": _dense(km[2], dm.ff, dm.d)}}


def train_init(dm: Dims, seed: int) -> Dict[str, Any]:
    """The float32 initial weights a training run starts from with this
    seed: embeddings N(0, 0.02), projections N(0, 2 / (fan_in + fan_out)),
    norms at one; the key split as the training cell's initialisation
    states it (embedding key, then one key per layer)."""
    k_emb, k_layers = jax.random.split(jax.random.PRNGKey(seed))
    layers = jax.vmap(lambda k: _layer(dm, k))(
        jax.random.split(k_layers, dm.layers))
    tok = jax.random.normal(jax.random.split(k_emb)[0],
                            (dm.vocab_rows, dm.d), jnp.float32) * 0.02
    return {"emb": {"tok": tok, "ln_f": jnp.ones((dm.d,), jnp.float32)},
            "layers": layers}


def serve_weights(dm: Dims, seed: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Serving weights made from the seed in the type they are served in:
    as ``train_init`` but with every norm at 1 + N(0, 0.1), so that a
    norm weight applied wrong shows."""
    w = train_init(dm, seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed ^ 0x5EED), 3))

    def norm(x):
        return 1.0 + 0.1 * jax.random.normal(next(keys), x.shape, x.dtype)

    w["layers"]["ln1"] = norm(w["layers"]["ln1"])
    w["layers"]["ln2"] = norm(w["layers"]["ln2"])
    w["emb"]["ln_f"] = norm(w["emb"]["ln_f"])
    return jax.tree.map(lambda x: x.astype(dtype), w)


# ----------------------------------------------------------------- model

def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return y.astype(x.dtype) * w


def rope(x, theta):
    """x [B, T, H, hd] at positions 0..T-1; rotate the two halves."""
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def attention(q, k, v, block: int):
    """Causal softmax attention, q/k/v [B, T, H, hd] (as many kv heads as
    q heads), over blocks of ``block`` query rows."""
    b, t, h, hd = q.shape
    block = min(block, t)
    qb = q.reshape(b, t // block, block, h, hd).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def one(args):
        i, qi = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k).astype(jnp.float32)
        s = s / math.sqrt(hd)
        rows = i * block + jnp.arange(block)
        s = jnp.where(rows[:, None] >= jnp.arange(t)[None, :], s, NEG)
        p = jax.nn.softmax(s, -1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = lax.map(one, (jnp.arange(t // block), qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, t, h, hd)


def block(dm: Dims, lp, h, attn_block: int = 512):
    b, t, _ = h.shape
    x = rms_norm(h, lp["ln1"], dm.eps)
    q = (x @ lp["attn"]["wq"]).reshape(b, t, dm.heads, dm.hd)
    k = (x @ lp["attn"]["wk"]).reshape(b, t, dm.kv_heads, dm.hd)
    v = (x @ lp["attn"]["wv"]).reshape(b, t, dm.kv_heads, dm.hd)
    rep = dm.heads // dm.kv_heads
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    o = attention(rope(q, dm.theta), rope(k, dm.theta), v, attn_block)
    h = h + dm.res_scale * (o.reshape(b, t, -1) @ lp["attn"]["wo"])
    x = rms_norm(h, lp["ln2"], dm.eps)
    m = lp["mlp"]
    return h + dm.res_scale * ((jax.nn.silu(x @ m["w1"]) * (x @ m["w3"]))
                               @ m["w2"])


def logits(dm: Dims, emb, h):
    """[..., d] -> [..., vocab_rows] float32; ids >= vocab masked out."""
    z = (rms_norm(h, emb["ln_f"], dm.eps) @ emb["tok"].T).astype(jnp.float32)
    return jnp.where(jnp.arange(dm.vocab_rows) < dm.vocab, z, NEG)


def loss(dm: Dims, params, tokens, labels, rows: int = 512):
    """Mean cross-entropy of next-token prediction, tokens/labels [B, T]."""
    h = params["emb"]["tok"][tokens]
    for i in range(dm.layers):
        lp = jax.tree.map(lambda x: x[i], params["layers"])
        h = jax.checkpoint(lambda lp, h: block(dm, lp, h))(lp, h)
    b, t, d = h.shape
    rows = min(rows, t)
    hb = h.reshape(b, t // rows, rows, d).transpose(1, 0, 2, 3)
    lb = labels.reshape(b, t // rows, rows).transpose(1, 0, 2)

    @jax.checkpoint
    def part(args):
        hi, li = args
        z = logits(dm, params["emb"], hi)
        logz = jax.nn.logsumexp(z, -1)
        gold = jnp.take_along_axis(z, li[..., None], -1)[..., 0]
        return jnp.sum(logz - gold)

    return jnp.sum(lax.map(part, (hb, lb))) / (b * t)


# ----------------------------------------------------------------- training

def _decays(name: str) -> bool:
    """Weight decay on every leaf but norms and biases."""
    leaf = name.rsplit("'", 2)[-2] if "'" in name else name
    return not (leaf.startswith("ln") or leaf.startswith("b")
                or "norm" in leaf)


def lr_at(o: Dict[str, Any], step: int) -> float:
    """Warmup, then a stable plateau, then a decay halving ten times
    (MiniCPM's WSD schedule)."""
    warm = min(step / max(o["warmup_steps"], 1), 1.0)
    total = float(o["total_steps"])
    stable_end = total * o["stable_frac"]
    tail = min(max((step - stable_end) / max(total - stable_end, 1.0), 0.0),
               1.0)
    return o["lr"] * warm * 0.5 ** (tail * 10.0)


def train_steps(dm: Dims, o: Dict[str, Any], params, batches: List[Tuple],
                dtype=jnp.float32):
    """AdamW with global-norm clipping over ``batches``, from ``params``.
    Returns the losses, the clipped gradient of the first step and the
    parameters after the last, all as the optimizer saw them."""
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    decays = [_decays(n) for n in names]
    b1, b2 = o["betas"]

    @jax.jit
    def grads(p, tokens, labels):
        return jax.value_and_grad(lambda p: loss(dm, p, tokens, labels))(p)

    @jax.jit
    def update(p, g, mu, nu, step, lr):
        g_leaves = jax.tree.leaves(g)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                             for x in g_leaves))
        scale = jnp.minimum(1.0, o["clip_norm"] / (gnorm + 1e-9))
        g_leaves = [(x.astype(jnp.float32) * scale).astype(dtype)
                    for x in g_leaves]
        treedef = jax.tree.structure(p)
        out_p, out_mu, out_nu = [], [], []
        for x, gx, m, n, dec in zip(jax.tree.leaves(p), g_leaves,
                                    jax.tree.leaves(mu), jax.tree.leaves(nu),
                                    decays):
            m = b1 * m + (1 - b1) * gx
            n = b2 * n + (1 - b2) * gx * gx
            u = (m / (1 - b1 ** step)) / (jnp.sqrt(n / (1 - b2 ** step))
                                          + o["eps"])
            if dec:
                u = u + o["weight_decay"] * x
            out_p.append((x - lr * u).astype(dtype))
            out_mu.append(m.astype(dtype))
            out_nu.append(n.astype(dtype))
        un = lambda leaves: jax.tree.unflatten(treedef, leaves)  # noqa: E731
        return un(out_p), un(g_leaves), un(out_mu), un(out_nu)

    p = jax.tree.map(lambda x: x.astype(dtype), params)
    mu = jax.tree.map(jnp.zeros_like, p)
    nu = jax.tree.map(jnp.zeros_like, p)
    losses, first_grad = [], None
    for i, (tokens, labels) in enumerate(batches, start=1):
        value, g = grads(p, jnp.asarray(tokens), jnp.asarray(labels))
        losses.append(float(value))
        p, g, mu, nu = update(p, g, mu, nu, float(i), lr_at(o, i))
        if first_grad is None:
            first_grad = g
        else:
            del g
    return losses, first_grad, p


# ----------------------------------------------------------------- serving

def _cast(quantize, dtype):
    def cast(x):
        if x.ndim > 1 and quantize is not None:
            x = quantize(x)
        return x.astype(dtype)
    return cast


@functools.partial(jax.jit, static_argnums=(2, 3))
def _serve_embed(tok, tokens, dtype, quantize):
    return _cast(quantize, dtype)(tok)[tokens]


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _serve_layer(dm: Dims, lp, h, dtype, quantize):
    return block(dm, jax.tree.map(_cast(quantize, dtype), lp), h,
                 attn_block=256)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _serve_head(dm: Dims, emb, h, dtype, quantize):
    return logits(dm, jax.tree.map(_cast(quantize, dtype), emb), h[:, -1])


def last_logits(dm: Dims, weights, tokens, dtype=jnp.float32,
                quantize=None):
    """Float32 logits after the last position of each prompt, tokens
    [B, T], computed one layer at a time from ``weights`` (stacked, any
    dtype) cast to ``dtype``.  ``quantize`` maps each matrix first (the
    control's lower precision)."""
    h = _serve_embed(weights["emb"]["tok"], jnp.asarray(tokens), dtype,
                     quantize)
    for i in range(dm.layers):
        layer = jax.tree.map(lambda x: x[i], weights["layers"])
        h = _serve_layer(dm, layer, h, dtype, quantize)
    return _serve_head(dm, weights["emb"], h, dtype, quantize)
