"""What the per-layer metrics read of the program's own spans: the
``repro.obs`` spans recorded in a traced run's window, summed by name.

A traced run's window runs under the jax profiler, and while the profiler
collects, the program keeps its spans (``obs.profiled()``); those that
lie inside the benchmark's ``window`` span are the run's.  A run that
hands over ``program_spans`` itself is read from those instead.  A
program without that recorder records nothing, and each reader returns
None, so its metric is left out of the result line."""

from __future__ import annotations

from typing import Any, Dict, List, Optional


def window_spans(run: Dict[str, Any]) -> List[Any]:
    """The program's spans that lie inside the run's ``window`` span."""
    if "program_spans" in run:
        return run["program_spans"]
    try:
        from repro import obs
    except ImportError:
        return []
    profiled = getattr(obs, "profiled", None)
    windows = [s for s in run["spans"] if s.name == "window"]
    if profiled is None or not windows:
        return []
    w = windows[-1]
    return [s for s in profiled()
            if w.start <= s.start and s.end <= w.end]


def totals(run: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    spans = window_spans(run)
    if not spans:
        return {}
    from repro import obs
    return obs.totals(spans)


def ms_per_cold_start(run: Dict[str, Any], name: str,
                      key: str = "seconds") -> Optional[float]:
    """``key`` (``seconds`` or ``self_seconds``) of the spans named
    ``name``, in ms, over the window's cold starts."""
    t = totals(run).get(name)
    n = run["result"].get("cold_starts")
    if t is None or not n:
        return None
    return 1e3 * t[key] / n


def ms_per_span(run: Dict[str, Any], name: str) -> Optional[float]:
    """The mean span named ``name``, in ms."""
    t = totals(run).get(name)
    if t is None:
        return None
    return 1e3 * t["seconds"] / t["count"]
