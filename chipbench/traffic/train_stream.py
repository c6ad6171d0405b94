"""A training job whose batches come through CFS.

Set-up writes a seeded corpus through ``ShardWriter`` into a replicated
volume, builds one ``Trainer`` over a ``ShardReader`` that the benchmark
wraps for its span, and drives it through its first steps (the compile
among them).  The window drives that same trainer, one ``Trainer.train(1)``
call per step, each ending in the trainer's own host read of the loss.

``correct`` compares, once the window has closed:
  * every batch the trainer consumed with the seeded corpus at the reader's
    addressing (exact);
  * the first steps' losses, the first clipped gradient (read from the
    optimizer's first moment after step 1) and the parameters' change over
    the first steps, leaf by leaf, with the plain float32 reference.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

import numpy as np

from chipbench import common

PRE_STEPS = 3      # steps the reference follows


class RecordingReader:
    """Delegates to the program's ``ShardReader``; spans and keeps each
    batch it hands out."""

    def __init__(self, reader, spans: common.Spans):
        self.inner = reader
        self.spans = spans
        self.batches: Dict[int, Dict[str, np.ndarray]] = {}

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        with self.spans.span("batch_at"):
            batch = self.inner.batch_at(step)
        self.batches[step] = batch
        return batch


# ------------------------------------------------------------------ corpus

def doc_lengths(p: Dict[str, Any]) -> np.ndarray:
    """Heavy-tailed document lengths (lognormal, capped), the same multiset
    for every seed: the seed only orders them."""
    total = p["shards"] * p["tokens_per_shard"]
    rng = np.random.default_rng(p["doc_length_seed"])
    lens = np.minimum(np.maximum(rng.lognormal(p["doc_log_mean"],
                                               p["doc_log_sigma"],
                                               size=total // 64), 16),
                      p["doc_max"]).astype(np.int64)
    lens = lens[: np.searchsorted(np.cumsum(lens), total) + 1]
    lens[-1] -= lens.sum() - total
    return lens


def corpus(p: Dict[str, Any], vocab: int, seed: int) -> np.ndarray:
    """The token stream the job trains on: seeded ids, each document ended
    by id 0."""
    rng = np.random.default_rng([seed, 1])
    toks = rng.integers(1, vocab, size=p["shards"] * p["tokens_per_shard"],
                        dtype=np.int32)
    lens = rng.permutation(doc_lengths(p))
    toks[np.cumsum(lens) - 1] = 0
    return toks


def reader_stream(toks: np.ndarray, p: Dict[str, Any]) -> np.ndarray:
    """The corpus in the order rank 0 of a world of 1 reads it: the shards
    in the order a ``RandomState(0)`` shuffle gives them."""
    order = list(range(p["shards"]))
    np.random.RandomState(0).shuffle(order)
    tps = p["tokens_per_shard"]
    return np.concatenate([toks[s * tps:(s + 1) * tps] for s in order])


def reference_batch(stream: np.ndarray, p: Dict[str, Any], step: int):
    """The batch for ``step``: the step's ``batch`` consecutive rows of
    ``seq + 1`` tokens of ``stream``, wrapping round at its end."""
    need = p["batch"] * (p["seq"] + 1)
    start = (step * need) % stream.size
    rows = np.take(stream, np.arange(start, start + need), mode="wrap")
    rows = rows.reshape(p["batch"], p["seq"] + 1)
    return rows[:, :-1], rows[:, 1:]


# ------------------------------------------------------------------ cell

def setup(cell) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from repro.core import CfsCluster
    from repro.storage.datapipe import ShardReader, ShardWriter
    from repro.train.trainer import Trainer, TrainerConfig

    p, spec = cell.params, cell.spec
    cfg = common.arch_config(spec)
    lay = spec["cluster"]
    cluster = CfsCluster(n_meta=lay["n_meta"], n_data=lay["n_data"],
                         extent_max_size=lay["extent_max_size"],
                         data_disk_capacity=lay["data_disk_capacity"])
    cluster.create_volume(lay["volume"], lay["meta_partitions"],
                          lay["data_partitions"], replicas=lay["replicas"])
    mnt = cluster.mount(lay["volume"])

    toks = corpus(p, cfg.vocab, cell.seed)
    t0 = time.perf_counter()
    writer = ShardWriter(mnt, "/data", tokens_per_shard=p["tokens_per_shard"])
    ends = np.flatnonzero(toks == 0) + 1
    for doc in np.split(toks, ends[:-1]):
        writer.add_document(doc.tolist())
    n = writer.finish()
    if n != p["shards"]:
        raise RuntimeError(f"wrote {n} shards, meant {p['shards']}")
    cell.say(f"corpus: {n} shards x {p['tokens_per_shard']} tokens, "
             f"{len(ends)} documents, written through CFS in "
             f"{time.perf_counter() - t0!r} s")

    reader = RecordingReader(
        ShardReader(mnt, "/data", rank=0, world=1, batch=p["batch"],
                    seq_len=p["seq"]), cell.spans)
    tc = TrainerConfig(ckpt_every=1 << 60, max_steps=1 << 60)
    trainer = Trainer(cfg, common.opt_config(cfg, spec), tc, mnt, reader,
                      seed=cell.seed, param_dtype=jnp.float32)
    n_params = sum(x.size for x in jax.tree.leaves(trainer.params))
    cell.say(f"model: {cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
             f"heads={cfg.n_heads}x{cfg.hd} ff={cfg.d_ff} vocab={cfg.vocab} "
             f"params={n_params} batch={p['batch']}x{p['seq']}")

    # the first steps: the compile, and what the reference follows
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v)))
                               for k, v in t.items()})
    b1 = common.opt_config(cfg, spec).betas[0]
    grad_norms = None
    for i in range(PRE_STEPS):
        t0 = time.perf_counter()
        trainer.train(1)
        cell.say(f"set-up step {trainer.step}: loss "
                 f"{trainer.history[-1]['loss']!r} wall_s "
                 f"{time.perf_counter() - t0!r}")
        if i == 0:
            mu = dict(zip(common.leaf_names(trainer.opt_state.mu),
                          jax.tree.leaves(trainer.opt_state.mu)))
            grad_norms = {k: float(v) / (1 - b1)
                          for k, v in norms(mu).items()}
    params = {k: np.array(v) for k, v in
              zip(common.leaf_names(trainer.params),
                  jax.tree.leaves(trainer.params))}
    return {"cluster": cluster, "mnt": mnt, "toks": toks, "reader": reader,
            "trainer": trainer, "cfg": cfg, "grad_norms": grad_norms,
            "params_after": params,
            "losses": [h["loss"] for h in trainer.history[:PRE_STEPS]]}


def window(cell, st: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    trainer, p = st["trainer"], cell.params
    step_s: List[float] = []
    first = trainer.step
    t_start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        with cell.spans.span("train_step"):
            trainer.train(1)
        t1 = time.perf_counter()
        step_s.append(t1 - t0)
        if t1 - t_start >= seconds:
            break
    elapsed = time.perf_counter() - t_start
    losses = [h["loss"] for h in trainer.history[first:]]
    tokens = len(step_s) * p["batch"] * p["seq"]
    st["window_steps"] = list(range(first, trainer.step))
    cell.say(f"window: {len(step_s)} steps in {elapsed!r} s; step wall s "
             f"min {min(step_s)!r} median {common.percentile(step_s, 50)!r} "
             f"max {max(step_s)!r}; last loss {losses[-1]!r}")
    return {"attempted": len(step_s),
            "failed": sum(not math.isfinite(x) for x in losses),
            "elapsed_s": elapsed, "steps": len(step_s), "tokens": tokens,
            "end_to_end": {
                "train_tokens_per_s": tokens / elapsed,
                "train_step_p88_ms": 1e3 * common.percentile(step_s, 88)}}


def release(cell, st: Dict[str, Any]) -> None:
    trainer = st.pop("trainer")
    common.delete_tree((trainer.params, trainer.opt_state))
    del trainer


def probe(cell) -> Dict[str, Any]:
    """What ``readings`` needs, without a window: set-up's first steps."""
    st = setup(cell)
    release(cell, st)
    return st


def readings(cell, st: Dict[str, Any], control: bool = False
             ) -> Dict[str, float]:
    """The numbers ``correct`` compares.  With ``control`` the reference in
    bfloat16 takes the program's place in the three step-readings."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import dense

    p, spec = cell.params, cell.spec
    dm = dense.Dims.of(spec)
    steps = st.get("window_steps", [])
    consumed = sorted(set(st["reader"].batches) | set(steps))
    stream = reader_stream(st["toks"], p)
    bad = 0
    for s in consumed:
        got = st["reader"].batches.get(s)
        want_t, want_l = reference_batch(stream, p, s)
        if got is None or not (np.array_equal(got["tokens"], want_t)
                               and np.array_equal(got["labels"], want_l)):
            bad += 1
    batches = [reference_batch(stream, p, s) for s in range(PRE_STEPS)]
    with jax.default_matmul_precision("highest"):
        p0 = jax.jit(dense.train_init, static_argnums=(0, 1))(dm, cell.seed)
        names = common.leaf_names(p0)
        p0_leaves = dict(zip(names, jax.tree.leaves(p0)))
        losses, g, p3 = dense.train_steps(dm, spec["optimizer"], p0, batches)
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    norm = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))))
    ref_grad = {n: float(norm(x)) for n, x in zip(names, jax.tree.leaves(g))}
    ref_move = {n: float(diff(x, p0_leaves[n]))
                for n, x in zip(names, jax.tree.leaves(p3))}
    del g, p3
    if control:
        got_losses, got_g, got_p3 = dense.train_steps(
            dm, spec["optimizer"], p0, batches, dtype=jnp.bfloat16)
        got_grad = {n: float(norm(x))
                    for n, x in zip(names, jax.tree.leaves(got_g))}
        got_move = {n: float(diff(x, p0_leaves[n]))
                    for n, x in zip(names, jax.tree.leaves(got_p3))}
        del got_g, got_p3
    else:
        got_losses = st["losses"]
        got_grad = st["grad_norms"]
        got_move = {n: float(diff(jnp.asarray(st["params_after"][n]),
                                  p0_leaves[n])) for n in names}
    # leaves the reference's gradient leaves at round-off move by it alone
    med = float(np.median(list(ref_grad.values())))
    keep = [n for n in names if ref_grad[n] >= 1e-3 * med]
    grad_gap, grad_at = common.worst_norm_gap(got_grad, ref_grad, keep)
    move_gap, move_at = common.worst_norm_gap(got_move, ref_move, keep)
    loss_gap = max(abs(a - b) for a, b in zip(got_losses, losses))
    cell.say(f"reference: losses {losses!r}; program {got_losses!r}; worst "
             f"gradient leaf {grad_at}, worst change leaf {move_at}; "
             f"{len(names) - len(keep)} leaves left out")
    return {"batches_wrong": float(bad), "loss_gap": loss_gap,
            "grad_gap": grad_gap, "update_gap": move_gap}
