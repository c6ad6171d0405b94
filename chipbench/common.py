"""What every cell of the chip benchmark shares.

Loading a cell by name (``workloads/<cell>.json`` and the configuration
file it names), the device check, host spans, the count of compiles, host
RSS and the leaf fingerprint.  Nothing here knows a cell, a configuration
or a metric by name: those are files of their own.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import resource
import time
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

# HuggingFace config keys -> the program's ArchConfig fields
_ARCH_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> Dict[str, Any]:
    return load_json(ROOT / "BENCHMARK.json")


def load_workload(name: str) -> Dict[str, Any]:
    """The cell's file, with its configuration's file under
    ``config_spec``."""
    wl = load_json(HERE / "workloads" / f"{name}.json")
    wl["name"] = name
    wl["config_spec"] = load_json(HERE / "configs" / f"{wl['config']}.json")
    return wl


def arch_config(spec: Dict[str, Any]):
    """The program's ArchConfig for a configuration file: the registry's
    entry for ``spec["arch"]`` with every size the file states."""
    import dataclasses as dc

    from repro.configs import get_arch
    sizes = {field: spec[key] for key, field in _ARCH_KEYS.items()
             if key in spec}
    return dc.replace(get_arch(spec["arch"]), **sizes)


def opt_config(cfg, spec: Dict[str, Any]):
    """The program's optimizer config with every value the file states."""
    from repro.train import optimizer as opt
    o = dict(spec["optimizer"])
    o["betas"] = tuple(o["betas"])
    return opt.opt_config_for(cfg, **o)


# ------------------------------------------------------------------ spans

@dataclasses.dataclass
class Span:
    name: str
    start: float          # host perf_counter seconds
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Spans:
    """Host spans from the benchmark's own files, around the calls into
    each layer.  With ``annotate`` each span is also a
    ``jax.profiler.TraceAnnotation`` named ``cb:<name>``, so the device
    trace can label its idle gaps by what the host was doing."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.items: List[Span] = []

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"cb:{name}")
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.items.append(Span(name, t0, time.perf_counter()))

    def named(self, name: str) -> List[Span]:
        return [s for s in self.items if s.name == name]

    def clear(self) -> None:
        self.items = []


class CompileCounter:
    """Counts the programs JAX lowers or compiles (a persistent-cache hit
    is lowered too) while it is switched on."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self.on and event in self.EVENTS:
            self.count += 1


def peak_host_rss_bytes() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def host_rss_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * resource.getpagesize()


def memory_line(after: str) -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return (f"memory after {after}: hbm_bytes_in_use="
            f"{stats.get('bytes_in_use')} peak_hbm_bytes="
            f"{stats.get('peak_bytes_in_use')} host_rss_bytes="
            f"{host_rss_bytes()} peak_host_rss_bytes={peak_host_rss_bytes()}")


# ------------------------------------------------------------------ leaves

def fingerprint(x):
    """Two 32-bit sums over the raw bits of a leaf, the second weighted by
    position: a changed, moved or missing element changes them.  Jitted by
    the caller; computed on the device, so nothing large crosses to the
    host."""
    import jax
    import jax.numpy as jnp
    width = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize]
    bits = jax.lax.bitcast_convert_type(x, width).astype(jnp.uint32).ravel()
    pos = jax.lax.iota(jnp.uint32, bits.size) * jnp.uint32(2654435761) + 1
    return jnp.stack([jnp.sum(bits, dtype=jnp.uint32),
                      jnp.sum(bits * pos, dtype=jnp.uint32)])


def leaf_names(tree) -> List[str]:
    import jax
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def delete_tree(tree) -> None:
    import jax
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "delete"):
            leaf.delete()


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it."""
    import math
    s = sorted(values)
    return s[max(0, math.ceil(q / 100 * len(s)) - 1)]


def worst_norm_gap(got: Dict[str, float], want: Dict[str, float],
                   keep: Optional[List[str]] = None) -> Tuple[float, str]:
    """The widest gap between two sets of per-leaf norms, each against the
    larger of that leaf's reference norm and the median leaf's."""
    import statistics
    names = keep if keep is not None else list(want)
    median = statistics.median(want[n] for n in names)
    worst, at = 0.0, ""
    for n in names:
        gap = abs(got[n] - want[n]) / max(want[n], median)
        if gap >= worst:
            worst, at = gap, n
    return worst, at
