#!/usr/bin/env python3
"""Smoke run of CFS's job path on one TPU chip.

    python chip_smoke.py [--seed N]

One process, no subprocess.  The phases, in order:

  device   the chip JAX sees, the JAX and libtpu versions, the compile
           cache in use;
  data     a CFS cluster and a token dataset written into it
           (``launch.train.build_cluster`` / ``write_dataset``);
  train    minicpm-2b at its published widths with the depth cut to
           LAYERS: a few steps on batches that ``ShardReader`` reads
           through CFS, then one checkpoint saved through
           ``CheckpointManager``;
  resume   the trainer's device state freed, a fresh ``Trainer`` resumed
           from CFS, and every leaf compared bit for bit with what was saved;
  serve    the restored params answering requests through ``BatchServer``;
  kernels  the four Pallas kernels, compiled for the chip, at real widths,
           each compared with its ``kernels/ref.py`` oracle;
  host     peak host RSS.

Every failed check raises, so the script exits non-zero before its last
line, which is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
There is no CPU path: without a TPU it exits non-zero with a message.  The
phase functions take their config, so tests call them on the CPU at
``.reduced()`` width.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import math
import resource
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.configs.base import ArchConfig  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import make_requests  # noqa: E402
from repro.launch.train import (GIB, arch_config, build_cluster,  # noqa: E402
                                make_trainer, write_dataset)
from repro.serve.server import BatchServer, Request  # noqa: E402
from repro.train.trainer import Trainer  # noqa: E402

ARCH = "minicpm-2b"
LAYERS = 2            # of 40: what one chip's HBM holds with the fp32 state
BATCH, SEQ, STEPS = 2, 1024, 5
# the kernels' real widths come from the configs whose layers they serve
KERNEL_ARCHS = {"flash_attention": "minicpm-2b", "wkv6": "rwkv6-1.6b",
                "mamba2_ssd": "zamba2-7b"}
# the kernel tests' tolerances (tests/test_kernels_pallas.py)
FLASH_TOL_BF16 = 2e-2
SCAN_TOL_F32 = 3e-3


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(line: str) -> None:
    print(line, flush=True)


def report_memory(after: str) -> None:
    """Device bytes in use and their peak (where the backend reports them),
    and host RSS now and at its peak."""
    stats = jax.devices()[0].memory_stats() or {}
    with open("/proc/self/statm") as f:
        rss = int(f.read().split()[1]) * resource.getpagesize()
    peak_rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    say(f"memory after {after}: hbm_bytes_in_use={stats.get('bytes_in_use')} "
        f"peak_hbm_bytes={stats.get('peak_bytes_in_use')} "
        f"host_rss_bytes={rss} peak_host_rss_bytes={peak_rss}")


# ----------------------------------------------------------------- phases

def phase_device() -> jax.Device:
    devices = jax.devices()
    dev = devices[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    say(f"versions: jax={jax.__version__} "
        f"jaxlib={importlib.metadata.version('jaxlib')} libtpu={libtpu}")
    say(f"compile_cache: {jax.config.jax_compilation_cache_dir}")
    return dev


def phase_data(cfg: ArchConfig, seed: int, disk_capacity: int):
    cluster = build_cluster(disk_capacity)
    mnt = cluster.mount("train")
    write_dataset(mnt, cfg.vocab, seed=seed)
    meta = json.loads(mnt.read_file("/data/META").decode())
    say(f"data: {meta['shards']} shards x {meta['tokens_per_shard']} tokens "
        "written through CFS")
    return mnt


def phase_train(cfg: ArchConfig, mnt, *, steps: int, batch: int, seq: int,
                seed: int):
    """Train ``steps`` steps, then save one checkpoint.  Returns the trainer
    and a host copy of the state it saved."""
    # the one checkpoint is saved explicitly below, after the steps
    trainer = make_trainer(cfg, mnt, steps=steps, batch=batch, seq=seq,
                           ckpt_every=steps + 1, seed=seed)
    n_params = sum(x.size for x in jax.tree.leaves(trainer.params))
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(trainer.state_tree()))
    say(f"model: {cfg.name} n_layers={cfg.n_layers} d_model={cfg.d_model} "
        f"n_heads={cfg.n_heads} head_dim={cfg.hd} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab} params={n_params} state_bytes={state_bytes}")
    say(f"train: batch={batch} seq={seq} steps={steps}, batches read "
        f"through CFS by ShardReader")
    for i in range(steps):
        trainer.train(1)            # ends in a host read of the loss
        h = trainer.history[-1]
        check(math.isfinite(h["loss"]) and math.isfinite(h["grad_norm"]),
              f"step {h['step']}: non-finite loss or grad norm {h}")
        if i == 0:
            # random init, logits of unit scale: the loss starts near ln V
            check(abs(h["loss"] - math.log(cfg.vocab)) < 2.0,
                  f"first loss {h['loss']} far from ln(vocab)")
        say(f"train step {h['step']}: loss {h['loss']!r} grad_norm "
            f"{h['grad_norm']!r}")

    report_memory("train")
    saved = jax.device_get(trainer.state_tree())
    report_memory("host copy of the state")
    d = trainer.ckpt.save(trainer.step, saved)
    manifest = json.loads(mnt.read_file(f"{d}/MANIFEST").decode())
    ckpt_bytes = sum(sh["bytes"] for t in manifest["tensors"].values()
                     for sh in t["shards"])
    check(trainer.ckpt.list_steps() == [trainer.step],
          f"expected one checkpoint, found {trainer.ckpt.list_steps()}")
    say(f"checkpoint: step {trainer.step} {ckpt_bytes} bytes in "
        f"{sum(len(t['shards']) for t in manifest['tensors'].values())} "
        "files, saved through CFS")
    return trainer, saved


_UINT_OF_WIDTH = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


@jax.jit
def _bits_equal(got: jax.Array, want: jax.Array) -> jax.Array:
    """Bit-for-bit equality, compared on the device: reading ``got`` back
    would keep a host copy of it cached on the array (6.5 GB for the whole
    state at the smoke's size).  Jitted, so the bit views are not copies."""
    bits = lambda x: jax.lax.bitcast_convert_type(  # noqa: E731
        x, _UINT_OF_WIDTH[x.dtype.itemsize])
    return jnp.array_equal(bits(got), bits(want))


def phase_resume(cfg: ArchConfig, mnt, trainer: Trainer, saved: Dict[str, Any],
                 *, steps: int, batch: int, seq: int, seed: int) -> Trainer:
    """Free ``trainer``'s device state, resume a fresh trainer from CFS and
    compare every restored leaf with ``saved`` bit for bit."""
    step = trainer.step
    for leaf in jax.tree.leaves((trainer.params, trainer.opt_state)):
        leaf.delete()
    del trainer
    # another init seed: a restore that changed nothing cannot pass
    fresh = make_trainer(cfg, mnt, steps=steps, batch=batch, seq=seq,
                         ckpt_every=steps + 1, seed=seed + 1)
    check(fresh.resume(), "no checkpoint to resume from")
    jax.block_until_ready(fresh.state_tree())
    report_memory("restore")
    check(fresh.step == step, f"resumed at step {fresh.step}, saved {step}")
    got = jax.tree_util.tree_flatten_with_path(fresh.state_tree())[0]
    want = jax.tree.leaves(saved)
    check(len(got) == len(want), "restored tree differs in structure")
    for (path, g), w in zip(got, want):
        name = jax.tree_util.keystr(path)
        check(g.dtype == w.dtype and g.shape == w.shape,
              f"{name}: restored {g.dtype}{g.shape}, saved {w.dtype}{w.shape}")
        check(bool(_bits_equal(g, w)),
              f"{name}: restored bits differ from the saved leaf")
    say(f"resume: fresh Trainer restored step {step} from CFS; {len(got)} "
        f"leaves equal the saved ones bit for bit")
    return fresh


def phase_serve(cfg: ArchConfig, params, *, n_requests: int, batch: int,
                min_prompt: int, max_prompt: int, max_new: int,
                seed: int) -> List[Request]:
    reqs = make_requests(cfg.vocab, n_requests, min_prompt, max_prompt,
                         max_new, seed=seed)
    srv = BatchServer(cfg, params, batch=batch, smax=max_prompt + max_new)
    done = srv.serve(reqs)
    check(sorted(r.rid for r in done) == list(range(n_requests)),
          f"served {sorted(r.rid for r in done)} of {n_requests} requests")
    for r in sorted(done, key=lambda r: r.rid):
        check(r.out is not None and len(r.out) == max_new,
              f"request {r.rid}: {len(r.out or [])} of {max_new} tokens")
        check(all(0 <= t < cfg.vocab for t in r.out),
              f"request {r.rid}: token outside [0, {cfg.vocab})")
        say(f"serve request {r.rid}: prompt {len(r.prompt)} tokens -> "
            f"{len(r.out)} tokens, first {r.out[:4]}")
    say(f"serve: {len(done)} requests in batches of {batch}")
    return done


def _max_err(got, want) -> float:
    return float(np.max(np.abs(np.asarray(got, np.float32)
                               - np.asarray(want, np.float32))))


def _within(got, want, tol: float) -> bool:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return bool(np.all(np.abs(got - want) <= tol + tol * np.abs(want)))


def _is_pallas_call(fn, *args, **kw) -> bool:
    """Whether ``fn`` lowers to a compiled Mosaic kernel on this backend."""
    return "tpu_custom_call" in fn.lower(*args, **kw).as_text()


def phase_kernels(cfgs: Dict[str, ArchConfig], *, batch: int, seq: int,
                  checksum_words: int, seed: int) -> Dict[str, Dict]:
    """Each Pallas kernel through ``kernels/ops.py`` against its oracle.
    ``cfgs`` gives each model kernel the config whose widths it runs at
    (keys as in ``KERNEL_ARCHS``).  Raises after reporting all four if any
    is beyond its tolerance.

    Oracles run at "highest" matmul precision: the TPU's default rounds f32
    operands to bf16.  The scans' oracles are the chunked references their
    kernel modules name (checked against the per-step scans on the CPU in
    tests/test_kernel_refs.py): on a v5e, XLA's per-step SSD scan is itself
    1.6e-2 off a float64 host scan at real widths, the chunked one 2.6e-4."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    normal = lambda shape, scale=1.0: scale * jax.random.normal(  # noqa: E731
        next(keys), shape, jnp.float32)
    out: Dict[str, Dict] = {}

    # flash attention, bf16, at the attention widths of its config
    flash_cfg = cfgs["flash_attention"]
    kvh, g, hd = (flash_cfg.n_kv_heads, flash_cfg.n_heads
                  // flash_cfg.n_kv_heads, flash_cfg.hd)
    q = normal((batch, seq, kvh, g, hd)).astype(jnp.bfloat16)
    k = normal((batch, seq, kvh, hd)).astype(jnp.bfloat16)
    v = normal((batch, seq, kvh, hd)).astype(jnp.bfloat16)
    got = ops.flash_attention(q, k, v, use_pallas=True)
    with jax.default_matmul_precision("highest"):
        want = ref.attention_naive(q.astype(jnp.float32),
                                   k.astype(jnp.float32),
                                   v.astype(jnp.float32))
    out["flash_attention"] = dict(
        shape=q.shape, tol=FLASH_TOL_BF16, max_err=_max_err(got, want),
        ok=_within(got, want, FLASH_TOL_BF16),
        compiled=_is_pallas_call(ops.flash_attention, q, k, v,
                                 use_pallas=True))

    # RWKV6 WKV scan at its config's head layout
    wkv_cfg = cfgs["wkv6"]
    h, kd = wkv_cfg.d_model // wkv_cfg.ssm_head_dim, wkv_cfg.ssm_head_dim
    r, kk, vv = (normal((batch, seq, h, kd), 0.5) for _ in range(3))
    w = jax.nn.sigmoid(normal((batch, seq, h, kd)) - 1.0)
    u = normal((h, kd), 0.3)
    got = ops.wkv6(r, kk, vv, w, u, use_pallas=True)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.rwkv6_chunked(r, kk, vv, w, u,
                                    jnp.zeros((batch, h, kd, kd), jnp.float32))
    out["wkv6"] = dict(
        shape=r.shape, tol=SCAN_TOL_F32, max_err=_max_err(got, want),
        ok=_within(got, want, SCAN_TOL_F32),
        compiled=_is_pallas_call(ops.wkv6, r, kk, vv, w, u, use_pallas=True))

    # Mamba2 SSD scan at its config's head layout
    ssd_cfg = cfgs["mamba2_ssd"]
    p = ssd_cfg.ssm_head_dim
    h, n = ssd_cfg.ssm_expand * ssd_cfg.d_model // p, ssd_cfg.ssm_state
    x = normal((batch, seq, h, p), 0.5)
    dt = jax.nn.softplus(normal((batch, seq, h)) - 1.0)
    a = -jnp.abs(normal((h,)))
    bm, cm = normal((batch, seq, n), 0.5), normal((batch, seq, n), 0.5)
    got = ops.mamba2_ssd(x, dt, a, bm, cm, use_pallas=True)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.mamba2_ssd(x, dt, a, bm, cm,
                                 jnp.zeros((batch, h, p, n), jnp.float32))
    out["mamba2_ssd"] = dict(
        shape=x.shape, tol=SCAN_TOL_F32, max_err=_max_err(got, want),
        ok=_within(got, want, SCAN_TOL_F32),
        compiled=_is_pallas_call(ops.mamba2_ssd, x, dt, a, bm, cm,
                                 use_pallas=True))

    # checksum over a checkpoint-sized buffer: exact
    data = jax.random.bits(next(keys), (checksum_words,), jnp.uint32)
    got = ops.tensor_checksum(data, use_pallas=True)
    want = ref.checksum(data)
    out["checksum"] = dict(
        shape=data.shape, tol=0, max_err=float(np.max(np.abs(
            np.asarray(got, np.int64) - np.asarray(want, np.int64)))),
        ok=bool(np.array_equal(np.asarray(got), np.asarray(want))),
        compiled=_is_pallas_call(ops.tensor_checksum, data, use_pallas=True))

    for name, res in out.items():
        say(f"kernel {name}: shape {tuple(res['shape'])} compiled="
            f"{res['compiled']} max_err {res['max_err']!r} "
            f"(tolerance {res['tol']}) {'ok' if res['ok'] else 'MISMATCH'}")
    bad = [name for name, res in out.items() if not res["ok"]]
    check(not bad, f"kernels beyond their tolerance: {bad}")
    return out


# ------------------------------------------------------------------- main

def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the dataset, the weights and the requests")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); this script runs only on a chip")
    enable_compile_cache()
    dev = phase_device()

    published = get_arch(ARCH)
    cfg = arch_config(ARCH, LAYERS)
    say("reduced: " + json.dumps(
        {"n_layers": f"{published.n_layers}->{cfg.n_layers}"}))
    # three replicas of a ~16 B/param checkpoint, spread over six nodes
    mnt = phase_data(cfg, args.seed, disk_capacity=16 * GIB)
    trainer, saved = phase_train(cfg, mnt, steps=STEPS, batch=BATCH,
                                 seq=SEQ, seed=args.seed)
    report_memory("checkpoint save")
    trainer = phase_resume(cfg, mnt, trainer, saved, steps=STEPS,
                           batch=BATCH, seq=SEQ, seed=args.seed)
    del saved
    params = trainer.params
    for leaf in jax.tree.leaves(trainer.opt_state):
        leaf.delete()
    del trainer
    report_memory("resume check")
    phase_serve(cfg, params, n_requests=8, batch=4, min_prompt=128,
                max_prompt=512, max_new=32, seed=args.seed)
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    del params
    phase_kernels({k: get_arch(a) for k, a in KERNEL_ARCHS.items()},
                  batch=BATCH, seq=SEQ, checksum_words=1 << 24, seed=args.seed)
    report_memory("kernels")
    say(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
