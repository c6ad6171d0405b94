"""Spans of host time inside the program: where a job's restore or read goes.

An operator records a job's spans and sums them by name::

    from repro import obs
    with obs.recording(annotate=True) as rec:
        tree, step = ckpt.restore(like, put=jnp.asarray)
    for name, t in obs.totals(rec.spans).items():
        print(name, t["count"], t["seconds"], t["self_seconds"])

With ``annotate`` each span is also a ``jax.profiler.TraceAnnotation``
named ``cfs:<name>``, so a profiler trace taken meanwhile shows the spans
on the device trace's clock, beside the chip's ops.

A job run under the jax profiler (``jax.profiler.trace`` or
``start_trace``) needs no ``recording``: while the profiler collects, a
span opened outside any recording is annotated the same way and kept,
and ``obs.profiled()`` returns the spans of the latest profiler trace::

    with jax.profiler.trace(trace_dir):
        t0 = time.perf_counter()
        tree, step = ckpt.restore(like, put=jnp.asarray)
    spans = [s for s in obs.profiled() if s.start >= t0]

Outside both a span is one shared do-nothing context: no clock is read and
nothing is kept, so the simulated cluster's results are the same with or
without it.  The clock is read here alone, never in the simulator.
"""

from __future__ import annotations

import contextlib
import itertools
import sys
import threading
from time import perf_counter
from typing import Any, Dict, Iterator, List, Optional

__all__ = ["Span", "Recorder", "span", "recording", "profiled",
           "totals"]


class _Null:
    """The span handed out when nothing records."""

    __slots__ = ()

    def __enter__(self) -> "_Null":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def add(self, **counts: int) -> None:
        return None


_NULL = _Null()


class Span:
    """One timed stretch: ``start``/``end`` on ``time.perf_counter``,
    ``parent`` the id of the span open around it on the same thread."""

    __slots__ = ("name", "id", "parent", "start", "end", "counts", "_rec",
                 "_ann")

    def __init__(self, rec: "Recorder", name: str, counts: Dict[str, int]):
        self._rec = rec
        self.name = name
        self.counts = counts
        self.end: Optional[float] = None

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def add(self, **counts: int) -> None:
        """Add to the span's counts (known only once its work is done)."""
        for k, v in counts.items():
            self.counts[k] = self.counts.get(k, 0) + v

    def __enter__(self) -> "Span":
        rec = self._rec
        try:
            stack = rec._local.stack
        except AttributeError:
            stack = rec._local.stack = []
        self.id = next(rec._ids)
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self._ann = None
        if rec._annotation is not None:
            self._ann = rec._annotation("cfs:" + self.name)
            self._ann.__enter__()
        self.start = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        rec = self._rec
        rec._local.stack.pop()
        rec.spans.append(self)


class Recorder:
    """The spans closed while it was active, in the order they closed."""

    def __init__(self, annotate: bool = False):
        self.spans: List[Span] = []
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._annotation = None
        if annotate:
            import jax
            self._annotation = jax.profiler.TraceAnnotation


_active: Optional[Recorder] = None
# the spans of the latest profiler trace, and whether a span has found the
# profiler off since they began (the next one found on starts anew)
_profiled: Optional[Recorder] = None
_profiler_was_off = True


def _profiler_on() -> bool:
    """Whether a jax profiler trace is collecting host events; False, and
    nothing imported, where jax's profiler was never loaded."""
    prof = sys.modules.get("jaxlib._profiler")
    return prof is not None and prof.TraceMe.is_enabled()


def _under_profiler() -> Optional[Recorder]:
    global _profiled, _profiler_was_off
    if not _profiler_on():
        _profiler_was_off = True
        return None
    if _profiler_was_off:
        _profiled, _profiler_was_off = Recorder(annotate=True), False
    return _profiled


def span(name: str, **counts: int):
    """A context manager timing its block as ``name`` with ``counts``
    (``bytes=``, ``attempts=`` ...), or the shared null context when
    nothing records."""
    rec = _active
    if rec is None:
        rec = _under_profiler()
        if rec is None:
            return _NULL
    return Span(rec, name, counts)


def profiled() -> List[Span]:
    """The spans closed outside any ``recording`` during the latest jax
    profiler trace (one still running included), in the order they
    closed."""
    return list(_profiled.spans) if _profiled is not None else []


@contextlib.contextmanager
def recording(annotate: bool = False) -> Iterator[Recorder]:
    """Record every span opened inside the block, on any thread."""
    global _active
    outer = _active
    rec = _active = Recorder(annotate)
    try:
        yield rec
    finally:
        _active = outer


def totals(spans: List[Span]) -> Dict[str, Dict[str, Any]]:
    """Per name: ``count``, ``seconds``, ``self_seconds`` (each span's
    duration less what its direct children cover) and each count summed."""
    child_s: Dict[int, float] = {}
    for s in spans:
        if s.parent is not None:
            child_s[s.parent] = child_s.get(s.parent, 0.0) + s.seconds
    out: Dict[str, Dict[str, Any]] = {}
    for s in spans:
        t = out.setdefault(s.name, {"count": 0, "seconds": 0.0,
                                    "self_seconds": 0.0})
        t["count"] += 1
        t["seconds"] += s.seconds
        t["self_seconds"] += s.seconds - child_s.get(s.id, 0.0)
        for k, v in s.counts.items():
            t[k] = t.get(k, 0) + v
    return out
