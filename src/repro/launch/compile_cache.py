"""Persistent XLA compile cache for the launchers and ``chip_smoke.py``.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` into ``jax_compilation_cache_dir``
by itself.  Where that is set it wins; otherwise the cache lives at one
fixed path inside the checkout, so every process started from it finds
what an earlier one compiled (the path is part of the cache key, so it is
never derived from a temp name, a pid or the time)."""

from __future__ import annotations

from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; call before
    the first compile.  Returns the directory in use."""
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
