"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

Random-inits params from a seed, then serves a queue of requests through
prefill + KV-cached decode.  Without ``--layers`` it runs the
``.reduced()`` config; with ``--layers N`` the published widths cut to N
layers.  ``chip_smoke.py`` builds its requests with ``make_requests`` and
serves params restored from a CFS checkpoint."""

from __future__ import annotations

import argparse
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_NAMES
from ..models import get_model
from ..serve.server import BatchServer, Request
from .compile_cache import enable_compile_cache
from .train import arch_config


def make_requests(vocab: int, n: int, min_prompt: int, max_prompt: int,
                  max_new: int, seed: int = 0) -> List[Request]:
    """``n`` requests whose prompts are random tokens, of lengths drawn
    uniformly from [min_prompt, max_prompt]."""
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(0, vocab, size=rng.randint(
                        min_prompt, max_prompt + 1)).tolist(),
                    max_new=max_new)
            for i in range(n)]


def main(argv: Optional[List[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="codeqwen1.5-7b", choices=ARCH_NAMES)
    ap.add_argument("--layers", type=int, default=None,
                    help="published widths cut to this many layers "
                         "(default: the reduced CPU-sized config)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2)
    args = ap.parse_args(argv)
    min_prompt, max_prompt = 5, 7

    enable_compile_cache()
    cfg = arch_config(args.arch, args.layers)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), jnp.float32)
    srv = BatchServer(cfg, params, batch=args.batch,
                      smax=max_prompt + args.max_new)
    reqs = make_requests(cfg.vocab, args.requests, min_prompt, max_prompt,
                         args.max_new)
    done = srv.serve(reqs)
    for r in sorted(done, key=lambda r: r.rid):
        print(f"req {r.rid}: prompt={r.prompt} -> {r.out}")
    print(f"served {len(done)} requests in batches of {args.batch}")


if __name__ == "__main__":
    main()
