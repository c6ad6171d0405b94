"""The profiler trace of a window, reduced to what the metrics read.

``start``/``stop`` run the profiler with the Python tracer off (it would
record every call of the file system's host code).  ``load`` reads the
``.xplane.pb`` the profiler wrote into plain lists:

    {"device": {"<plane>": [[op, start_ns, duration_ns], ...]},
     "host":   [[span, start_ns, duration_ns], ...]}

``device`` holds the ops of each TPU plane (its "XLA Ops" line); ``host``
holds the benchmark's own spans (``cb:<name>`` annotations) on the same
clock.  ``reduce`` works from that form alone, so a small recorded one is
a test fixture.

Busy time is the union of a chip's op intervals inside the window (the
``cb:window`` span), averaged over the chips; each stretch in which no op
runs is an idle gap, labelled with the innermost benchmark span that
covers it (cut at the spans' edges, each gap goes under the label that
holds most of it).
"""

from __future__ import annotations

import collections
import glob
import os
from typing import Any, Dict, List, Tuple

WINDOW = "cb:window"
TOP = 10


def start(trace_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(trace_dir: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out: Dict[str, Any] = {"device": {}, "host": []}
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:TPU"):
            line = lines.get("XLA Ops")
            if line is not None:
                # an op's name is its HLO text: keep the instruction's name
                out["device"][plane.name] = [
                    [e.name.split(" = ", 1)[0], int(e.start_ns),
                     int(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, int(e.start_ns), int(e.duration_ns)]
                                for e in line.events
                                if e.name.startswith("cb:")]
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _innermost(spans: List[Tuple[str, int, int]], t: float) -> str:
    best, width = "host", None
    for name, s, e in spans:
        if s <= t <= e and (width is None or e - s < width):
            best, width = name[len("cb:"):], e - s
    return best


def _labelled(spans: List[Tuple[str, int, int]], s: int, e: int
              ) -> Dict[str, float]:
    """Seconds of the stretch [s, e] under each label: the stretch is cut
    at every span's edge, and each piece goes to the innermost span over
    it ("host" where the benchmark had none open)."""
    cuts = sorted({s, e} | {t for _, a, b in spans for t in (a, b)
                            if s < t < e})
    out: Dict[str, float] = collections.defaultdict(float)
    for a, b in zip(cuts, cuts[1:]):
        out[_innermost(spans, (a + b) / 2)] += (b - a) / 1e9
    return out


def reduce(ev: Dict[str, Any]) -> Dict[str, Any]:
    """Busy and window seconds, idle seconds by label, and the breakdown:
    the ops that took most device time and the longest idle gaps."""
    windows = [(s, s + d) for n, s, d in ev["host"] if n == WINDOW]
    if not windows:
        raise ValueError("the trace has no cb:window span")
    w0, w1 = windows[-1]
    spans = [(n, s, s + d) for n, s, d in ev["host"] if n != WINDOW]
    op_time: Dict[str, float] = collections.defaultdict(float)
    busy, gaps = [], []
    for ops in ev["device"].values():
        inside = [(max(s, w0), min(s + d, w1), n) for n, s, d in ops
                  if s + d > w0 and s < w1]
        for s, e, n in inside:
            op_time[n] += (e - s) / 1e9
        merged = _union([(s, e) for s, e, _ in inside])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edge = w0
        for s, e in merged + [(w1, w1)]:
            if s > edge:
                gaps.append(_labelled(spans, edge, s))
            edge = max(edge, e)
    n_chips = max(len(ev["device"]), 1)
    idle_by: Dict[str, float] = collections.defaultdict(float)
    for pieces in gaps:
        for label, sec in pieces.items():
            idle_by[label] += sec / n_chips
    # each gap under the label that holds most of it
    gaps = [(max(g, key=g.get), sum(g.values())) for g in gaps]
    return {
        "busy_s": sum(busy) / n_chips,
        "window_s": (w1 - w0) / 1e9,
        "chips": len(ev["device"]),
        "idle_by_label": dict(idle_by),
        "breakdown": {
            "device_ops": [[n, t / n_chips] for n, t in sorted(
                op_time.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[n, t] for n, t in sorted(
                gaps, key=lambda g: -g[1])[:TOP]]},
    }
