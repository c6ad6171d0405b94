"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Builds a CFS cluster, writes a token dataset into it, and trains with
checkpointing THROUGH the file system, optionally crash+resuming.  Without
``--layers`` it runs the ``.reduced()`` config (width 128, CPU-sized); with
``--layers N`` it runs the published widths cut to N layers, the size
``chip_smoke.py`` drives on one chip through these same functions.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import List, Optional

import numpy as np

from ..configs import ARCH_NAMES, get_arch
from ..configs.base import ArchConfig
from ..core import CfsCluster
from ..storage.checkpoint import InjectedCrash
from ..storage.datapipe import ShardReader, ShardWriter
from ..train import optimizer as opt
from ..train.trainer import Trainer, TrainerConfig
from .compile_cache import enable_compile_cache

GIB = 1024 ** 3


def arch_config(arch: str, layers: Optional[int] = None) -> ArchConfig:
    """The published config cut to ``layers`` layers, or its ``.reduced()``
    CPU-sized form when ``layers`` is None."""
    cfg = get_arch(arch)
    if layers is None:
        return cfg.reduced()
    return dataclasses.replace(cfg, n_layers=layers)


def build_cluster(disk_capacity: int = 4 * GIB) -> CfsCluster:
    """Six data nodes of ``disk_capacity`` bytes each; volume "train" keeps
    three replicas of every extent."""
    c = CfsCluster(n_meta=4, n_data=6, extent_max_size=1024 * 1024,
                   data_disk_capacity=disk_capacity)
    c.create_volume("train", n_meta_partitions=3, n_data_partitions=8)
    return c


def write_dataset(mnt, vocab: int, n_docs: int = 8, seed: int = 0) -> None:
    w = ShardWriter(mnt, "/data", tokens_per_shard=8192)
    rng = np.random.RandomState(seed)
    for _ in range(n_docs):
        start = rng.randint(0, min(vocab, 97))
        w.add_document([(start + 3 * i) % min(vocab, 97)
                        for i in range(4000)])
    w.finish()


def make_trainer(cfg: ArchConfig, mnt, *, steps: int, batch: int, seq: int,
                 ckpt_every: int, seed: int = 0) -> Trainer:
    oc = opt.opt_config_for(cfg, lr=1e-3, warmup_steps=5, total_steps=steps)
    tc = TrainerConfig(ckpt_every=ckpt_every, max_steps=steps)
    reader = ShardReader(mnt, "/data", rank=0, world=1,
                         batch=batch, seq_len=seq)
    return Trainer(cfg, oc, tc, mnt, reader, seed=seed)


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b", choices=ARCH_NAMES)
    ap.add_argument("--layers", type=int, default=None,
                    help="published widths cut to this many layers "
                         "(default: the reduced CPU-sized config)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--crash-at", type=int, default=None,
                    help="inject a crash at this step, then auto-resume")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = arch_config(args.arch, args.layers)
    print(f"arch={cfg.name} ({cfg.n_layers}L d={cfg.d_model})")
    cluster = build_cluster()
    mnt = cluster.mount("train")
    write_dataset(mnt, cfg.vocab)

    def trainer_for_run() -> Trainer:
        return make_trainer(cfg, mnt, steps=args.steps, batch=args.batch,
                            seq=args.seq, ckpt_every=args.ckpt_every)

    trainer = trainer_for_run()
    try:
        trainer.train(args.steps, crash_at=args.crash_at)
    except InjectedCrash as e:
        print(f"!! {e} — resuming from CFS checkpoint")
        trainer = trainer_for_run()
        assert trainer.resume(), "no checkpoint to resume from"
        print(f"resumed at step {trainer.step}")
        trainer.train(args.steps - trainer.step)

    for h in trainer.history:
        print(f"step {h['step']:4d}  loss {h['loss']:.4f}  "
              f"|g| {h['grad_norm']:.3f}")
    print(f"checkpoints on volume: {trainer.ckpt.list_steps()}")


if __name__ == "__main__":
    main()
