"""The readers of the program's own spans (``repro.obs``): on a span list
made by hand (two cold starts, each a restore of 10 s, and two waves), on
spans the program kept under the profiler, and in a traced run of the
cold-start cell."""

import time

import jax
import pytest

from chipbench import common, run
from chipbench.tests.helpers import bench_with, small_workload
from repro import obs

NEW = {"ckpt_restore_ms.cold": 10_000.0, "ckpt_crc_ms.cold": 2_000.0,
       "ckpt_decode_ms.cold": 1_500.0, "ckpt_put_ms.cold": 1_000.0,
       "client_open_ms.cold": 500.0, "client_read_self_ms.cold": 1_000.0,
       "client_fetch_self_ms.cold": 500.0,
       "datanode_read_ms.cold": 1_500.0, "server_wave_ms.cold": 550.0}
SELF_TIMES = ("client_open_ms.cold", "client_read_self_ms.cold",
              "client_fetch_self_ms.cold", "datanode_read_ms.cold",
              "ckpt_crc_ms.cold", "ckpt_decode_ms.cold", "ckpt_put_ms.cold")


def _spans():
    out, ids = [], iter(range(1, 1000))

    def add(name, start, end, parent=None, **counts):
        s = obs.Span(None, name, counts)
        s.id, s.parent, s.start, s.end = next(ids), parent, start, end
        out.append(s)
        return s.id

    for k, t in enumerate((0.0, 20.0)):
        r = add("ckpt.restore", t, t + 10, bytes=100)
        add("client.open", t, t + 0.5, r)
        rd = add("client.read", t + 0.5, t + 3.5, r, bytes=100)
        f = add("client.fetch", t + 0.5, t + 2.5, rd, bytes=100, attempts=1)
        add("datanode.read", t + 1.0, t + 2.5, f, bytes=100)
        add("ckpt.crc32", t + 3.5, t + 5.5, r, bytes=100)
        add("ckpt.decode", t + 5.5, t + 7.0, r, bytes=100)
        add("ckpt.put", t + 7.0, t + 8.0, r, bytes=100)
        add("server.wave", t + 10, t + 10.6 - 0.1 * k, slots=8, tokens=8)
    return out


def _read(name, spans, cold_starts=2):
    reader = run.load_module(run.HERE / "metrics" / f"{name}.py")
    return reader.read({"result": {"cold_starts": cold_starts},
                        "spans": [], "program_spans": spans})


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_on_a_span_list_by_hand(name):
    assert _read(name, _spans()) == pytest.approx(NEW[name])


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_without_program_spans_reports_nothing(name):
    """A program without ``repro.obs`` records nothing: the metric is left
    out, not read as zero."""
    assert _read(name, []) is None


def test_self_times_split_the_restore():
    spans = _spans()
    parts = sum(_read(n, spans) for n in SELF_TIMES)
    restore = _read("ckpt_restore_ms.cold", spans)
    # the rest is the restore's own loop (2 s of each 10 s here)
    assert parts + 2_000.0 == pytest.approx(restore)


def test_every_new_metric_is_in_the_benchmark_for_the_cold_start():
    from chipbench import common
    bench = common.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = entries[name]
        assert m["workloads"] == ["minicpm2b-cold-start"]
        assert (m["moves"], m["unit"], m["better"]) == (
            "cold_ttft_s", "ms", "lower")
        assert (run.HERE / "metrics" / f"{name}.py").exists()


def test_readers_take_the_profiled_spans_inside_the_window(tmp_path):
    """A traced run hands its readers no spans of the program: they read
    the spans the program kept while the profiler collected, those inside
    the benchmark's ``window`` span."""
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("ckpt.restore", bytes=1):       # before the window
            pass
        w0 = time.perf_counter()
        for _ in range(3):
            with obs.span("ckpt.restore", bytes=2):
                with obs.span("ckpt.crc32", bytes=2):
                    pass
        w1 = time.perf_counter()
    window = common.Span("window", w0, w1)
    got = {n: run.load_module(run.HERE / "metrics" / f"{n}.py").read(
        {"result": {"cold_starts": 3}, "spans": [window]})
        for n in ("ckpt_restore_ms.cold", "ckpt_crc_ms.cold")}
    inside = [s for s in obs.profiled() if w0 <= s.start]
    assert len(inside) == 6 and len(obs.profiled()) == 7
    want = obs.totals(inside)
    assert got["ckpt_restore_ms.cold"] == pytest.approx(
        1e3 * want["ckpt.restore"]["seconds"] / 3)
    assert got["ckpt_crc_ms.cold"] == pytest.approx(
        1e3 * want["ckpt.crc32"]["seconds"] / 3)
    # a run without a window span reads nothing
    reader = run.load_module(run.HERE / "metrics" / "ckpt_crc_ms.cold.py")
    assert reader.read({"result": {"cold_starts": 3}, "spans": []}) is None


def test_traced_cold_start_reports_the_program_spans():
    """The nine metrics in a traced run of the cell, and how they add up:
    the self times lie inside the restore, the restore inside the
    benchmark's span around it, the client's time inside the mount's."""
    wl = "minicpm2b-cold-start"
    out = run.run_cell(small_workload(wl), seed=2 ** 31 + 29, seconds=1.0,
                       trace=True, require_tpu=False, bench=bench_with(wl),
                       t_start=time.perf_counter())
    assert out["correct"], out["checks"]
    ms = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW) <= set(ms)
    inside = sum(ms[k] for k in SELF_TIMES)
    assert 0 < inside <= ms["ckpt_restore_ms.cold"] * (1 + 1e-9)
    assert ms["ckpt_restore_ms.cold"] <= ms["restore_ms.cold"]
    assert ms["client_open_ms.cold"] + ms["client_read_self_ms.cold"] <= \
        ms["cfs_read_ms.cold"]
    assert 0 < ms["server_wave_ms.cold"] <= ms["first_wave_ms.cold"]
