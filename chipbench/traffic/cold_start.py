"""A serving replica that starts cold from weights stored in CFS.

Set-up makes the serving weights on the device from the seed, in the type
they are served in, warms the server's prefill on them, copies them to the
host and saves them through ``CheckpointManager`` into a replicated volume:
a replica can only start cold from weights that already exist.  It then
frees the device copy.

The window runs cold starts back to back: a new client mounts the volume
(wrapped by the benchmark for its span), ``CheckpointManager.restore``
reads the weights through it onto the chip, then
``BatchServer.serve`` of the wave of requests queued meanwhile, then the
weights are freed.  The cold start under way when the window's time is up
is finished and counted.

``correct`` compares, once the window has closed:
  * each restored leaf's fingerprint with the saved leaf's (exact);
  * every served token with the plain float32 reference's logits after
    the same prompt: the widest gap by which a served token's logit lies
    below the reference's best.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from chipbench import common

WARM = 1 << 30       # the wave index of the set-up's warm-up wave


class SpanMount:
    """Delegates to the program's mount; spans each ``read_file``."""

    def __init__(self, mount, spans: common.Spans):
        self._mnt = mount
        self._spans = spans

    def read_file(self, path: str) -> bytes:
        with self._spans.span("cfs_read"):
            return self._mnt.read_file(path)

    def __getattr__(self, name: str):
        return getattr(self._mnt, name)


def prompts(p: Dict[str, Any], vocab: int, seed: int, k: int) -> np.ndarray:
    """The wave queued for cold start ``k``: ``slots`` seeded prompts of
    ``prompt_len`` tokens."""
    rng = np.random.default_rng([seed, 2, k])
    return rng.integers(0, vocab, size=(p["slots"], p["prompt_len"]),
                        dtype=np.int32)


def _requests(p, toks: np.ndarray):
    from repro.serve.server import Request
    return [Request(rid=i, prompt=row.tolist(), max_new=p["max_new"])
            for i, row in enumerate(toks)]


def _weights(cell):
    import jax

    from chipbench.reference import dense
    dm = dense.Dims.of(cell.spec)
    return jax.jit(dense.serve_weights, static_argnums=(0, 1))(dm, cell.seed)


def setup(cell) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from repro.core import CfsCluster
    from repro.storage.checkpoint import CheckpointManager

    p, spec = cell.params, cell.spec
    cfg = common.arch_config(spec)
    lay, ck = spec["cluster"], spec["checkpoint"]
    cluster = CfsCluster(n_meta=lay["n_meta"], n_data=lay["n_data"],
                         extent_max_size=lay["extent_max_size"],
                         data_disk_capacity=lay["data_disk_capacity"])
    cluster.create_volume(lay["volume"], lay["meta_partitions"],
                          lay["data_partitions"], replicas=lay["replicas"])
    mnt = cluster.mount(lay["volume"])

    w = _weights(cell)
    jax.block_until_ready(w)
    fp = jax.jit(lambda t: jax.tree.map(common.fingerprint, t))
    saved_fp = np.asarray(jax.tree.leaves(jax.device_get(fp(w))))
    n_params = sum(x.size for x in jax.tree.leaves(w))
    cell.say(f"model: {cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
             f"heads={cfg.n_heads}x{cfg.hd} ff={cfg.d_ff} vocab={cfg.vocab} "
             f"params={n_params} bytes="
             f"{sum(x.nbytes for x in jax.tree.leaves(w))}")

    # one server for the replica's life; its prefill warmed on the weights
    srv = server_for(cfg, w, p)
    t0 = time.perf_counter()
    srv.serve(_requests(p, prompts(p, cfg.vocab, cell.seed, WARM)))
    cell.say(f"warm-up wave: {time.perf_counter() - t0!r} s")
    like = jax.eval_shape(lambda: {"params": w})
    host = {"params": jax.tree.map(np.asarray, w)}
    common.delete_tree(w)
    srv.params = w = None
    cell.say(common.memory_line("host copy of the weights"))

    ckpt = CheckpointManager(mnt, ck["base"], shards=ck["shards"])
    t0 = time.perf_counter()
    ckpt.save(0, host)
    del host
    cell.say(f"weights saved through CFS in {time.perf_counter() - t0!r} s")
    cell.say(common.memory_line("save"))
    return {"cluster": cluster, "srv": srv, "like": like,
            "fp": fp, "saved_fp": saved_fp, "cfg": cfg, "served": []}


def window(cell, st: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from repro.storage.checkpoint import CheckpointManager

    p, srv, cfg = cell.params, st["srv"], st["cfg"]
    lay, ck = cell.spec["cluster"], cell.spec["checkpoint"]
    cold_s: List[float] = []
    t_start = time.perf_counter()
    k = 0
    while True:
        reqs = _requests(p, prompts(p, cfg.vocab, cell.seed, k))
        gc.collect()
        t0 = time.perf_counter()
        with cell.spans.span("restore"):
            # a new replica: a new client, with nothing of CFS cached
            mnt = SpanMount(st["cluster"].mount(lay["volume"]), cell.spans)
            ckpt = CheckpointManager(mnt, ck["base"], shards=ck["shards"])
            tree, _ = ckpt.restore(st["like"], put=jnp.asarray)
            jax.block_until_ready(tree)
        srv.params = tree["params"]
        with cell.spans.span("serve"):
            done = srv.serve(reqs)
        t1 = time.perf_counter()
        cold_s.append(t1 - t0)
        got_fp = np.asarray(jax.tree.leaves(jax.device_get(
            st["fp"](tree["params"]))))
        st["served"].append({"k": k, "fp": got_fp,
                             "out": {r.rid: list(r.out or []) for r in done}})
        common.delete_tree(tree)
        srv.params = tree = None
        k += 1
        if t1 - t_start >= seconds:
            break
    elapsed = time.perf_counter() - t_start
    answered = sum(len(s["out"]) for s in st["served"])
    cell.say(f"window: {len(cold_s)} cold starts in {elapsed!r} s: "
             f"{cold_s!r}")
    return {"attempted": len(cold_s) * p["slots"],
            "failed": len(cold_s) * p["slots"] - answered,
            "elapsed_s": elapsed, "cold_starts": len(cold_s),
            "end_to_end": {"cold_ttft_s": sum(cold_s) / len(cold_s)}}


def probe(cell, waves: int = 2) -> Dict[str, Any]:
    """What ``readings`` needs, from the server alone: the seed's weights
    made on the device and handed to ``BatchServer``, ``waves`` waves
    served, as many as a run's window serves.  The restore that a run puts
    in front of the server is compared bit for bit on its own."""
    import jax

    p = cell.params
    cfg = common.arch_config(cell.spec)
    w = _weights(cell)
    fp = np.asarray(jax.tree.leaves(jax.device_get(jax.jit(
        lambda t: jax.tree.map(common.fingerprint, t))(w))))
    srv = server_for(cfg, w, p)
    served = []
    for k in range(waves):
        done = srv.serve(_requests(p, prompts(p, cfg.vocab, cell.seed, k)))
        served.append({"k": k, "fp": fp,
                        "out": {r.rid: list(r.out or []) for r in done}})
    common.delete_tree(w)
    return {"saved_fp": fp, "served": served}


def server_for(cfg, weights, p):
    from repro.serve.server import BatchServer
    return BatchServer(cfg, weights, batch=p["slots"],
                       smax=p["prompt_len"] + p["max_new"])


def release(cell, st: Dict[str, Any]) -> None:
    st.pop("srv")
    st.pop("cluster")


def fp8(x):
    """A matrix through float8 e4m3 with one scale per tensor."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(x.astype(jnp.float32))) / 448.0
    return (x.astype(jnp.float32) / s).astype(jnp.float8_e4m3fn
                                              ).astype(jnp.float32) * s


def readings(cell, st: Dict[str, Any], control: bool = False
             ) -> Dict[str, float]:
    """The numbers ``correct`` compares.  With ``control`` the reference
    with float8 weights takes the server's place: its first choice at each
    prompt stands for the served token."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import dense

    p = cell.params
    dm = dense.Dims.of(cell.spec)
    bad_leaves = sum(int(np.any(s["fp"] != st["saved_fp"]))
                     for s in st["served"])
    w = _weights(cell)
    gap, missing = 0.0, 0
    for s in st["served"]:
        toks = prompts(p, dm.vocab, cell.seed, s["k"])
        with jax.default_matmul_precision("highest"):
            want = np.asarray(dense.last_logits(dm, w, toks))
        if control:
            got = np.asarray(dense.last_logits(dm, w, toks, quantize=fp8))
            first = np.argmax(got[:, :dm.vocab], -1)
        else:
            first = []
            for i in range(len(toks)):
                out = s["out"].get(i, [])
                if not out or not 0 <= out[0] < dm.vocab:
                    missing += 1
                    first.append(int(np.argmin(want[i, :dm.vocab])))
                else:
                    first.append(out[0])
        best = want[:, :dm.vocab].max(-1)
        gap = max(gap, float(np.max(best - want[np.arange(len(toks)),
                                                 first])))
    common.delete_tree(w)
    cell.say(f"reference: {len(st['served'])} waves compared; widest gap "
             f"{gap!r}")
    return {"weights_wrong": float(bad_leaves), "answers_missing":
            float(missing), "token_gap": gap}
