"""The trace reduction on a small trace in ``load``'s form."""

import json
from pathlib import Path

import pytest

from chipbench import trace

FIXTURE = Path(__file__).parent / "fixtures" / "trace_two_steps.json"


def test_busy_share_and_labelled_gaps():
    r = trace.reduce(json.loads(FIXTURE.read_text()))
    # ops 150-350, 340-400 and 650-900 ms; the window is 100-1100 ms
    assert r["window_s"] == pytest.approx(1.0)
    assert r["busy_s"] == pytest.approx(0.2 + 0.05 + 0.25)
    assert r["chips"] == 1
    # idle 100-150 ms (under batch_at), 400-650 ms (150 ms of it still in
    # the first train_step, 100 ms in the second step's batch_at) and
    # 900-1100 ms (50 ms in the second train_step, then no span)
    assert r["breakdown"]["idle_gaps"] == [
        ["train_step", pytest.approx(0.25)], ["host", pytest.approx(0.2)],
        ["batch_at", pytest.approx(0.05)]]
    assert r["idle_by_label"] == {"batch_at": pytest.approx(0.15),
                                  "train_step": pytest.approx(0.2),
                                  "host": pytest.approx(0.15)}
    ops = dict((n, t) for n, t in r["breakdown"]["device_ops"])
    assert ops == {"fusion.1": pytest.approx(0.45),
                   "dot.2": pytest.approx(0.06)}


def test_a_trace_without_the_window_is_refused():
    ev = json.loads(FIXTURE.read_text())
    ev["host"] = [h for h in ev["host"] if h[0] != "cb:window"]
    with pytest.raises(ValueError):
        trace.reduce(ev)


RECORDED = Path(__file__).parent / "fixtures" / "trace_recorded.json"


def test_recorded_trace_against_a_time_grid():
    """A window cut from a chip's trace: busy time equals a count of the
    microseconds in which some op ran, and every gap's label is a span the
    benchmark opened."""
    import numpy as np
    ev = json.loads(RECORDED.read_text())
    r = trace.reduce(ev)
    (_, w0, wd), = [h for h in ev["host"] if h[0] == trace.WINDOW]
    grid = np.zeros(wd // 1000 + 1, bool)
    for ops in ev["device"].values():
        for _, s, d in ops:
            a, b = max(s, w0), min(s + d, w0 + wd)
            if b > a:
                grid[(a - w0) // 1000:(b - w0) // 1000] = True
    assert r["busy_s"] == pytest.approx(grid.sum() / 1e6, abs=2e-4)
    assert 0 < r["busy_s"] < r["window_s"]
    spans = {h[0][len("cb:"):] for h in ev["host"]} | {"host"}
    assert {g[0] for g in r["breakdown"]["idle_gaps"]} <= spans
    assert sum(r["idle_by_label"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
