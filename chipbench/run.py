#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: load and warm up (``setup_s``), measure for ``--seconds``,
check what the timed path produced against the plain reference, print the
result as the last line of standard output.  With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the
window runs under the profiler and the result carries its per-layer
metrics, the device's busy and window seconds and a breakdown.

Everything a cell needs is found by name: ``workloads/<cell>.json`` names
its configuration (``configs/<config>.json``), its traffic
(``traffic/<traffic>.py``) and the limits of its comparison; each
per-layer metric is ``metrics/<metric>.py``; ``BENCHMARK.json`` says which
metrics the cell reports.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE.parent))

from chipbench import common  # noqa: E402


class NoChip(SystemExit):
    """The machine lacks the chips the cell asks for."""


@dataclasses.dataclass
class Cell:
    """One cell as a run sees it."""
    name: str
    seed: int
    params: Dict[str, Any]          # the traffic's parameters
    spec: Dict[str, Any]            # the configuration file
    limits: Dict[str, float]
    spans: common.Spans
    say: Callable[[str], None]


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def say(line: str) -> None:
    print(line, flush=True)


def cell_metrics(bench: Dict[str, Any], cell: str, kind: str) -> List[Dict]:
    """The ``kind`` metrics of BENCHMARK.json that ``cell`` reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def device_check(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"chipbench: JAX found no TPU (platform "
                     f"{devices[0].platform!r}); the benchmark runs only on "
                     f"a chip")
    if len(devices) < chips:
        raise NoChip(f"chipbench: the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def peaks_for(kind: str, require_tpu: bool) -> Optional[Dict[str, Any]]:
    table = common.load_json(HERE / "peaks.json")["devices"]
    if kind in table:
        return table[kind]
    if require_tpu:
        raise SystemExit(f"chipbench: no peaks for device_kind {kind!r} in "
                         f"chipbench/peaks.json")
    return None


def run_cell(workload: Dict[str, Any], *, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True,
             bench: Optional[Dict[str, Any]] = None,
             t_start: Optional[float] = None) -> Dict[str, Any]:
    """Run one cell; returns the result line's object.  ``setup_s`` counts
    from ``t_start`` (the process's start when run as a script)."""
    import jax

    from chipbench import trace as tracing

    bench = bench or common.load_benchmark()
    name = workload["name"]
    entry = next(w for w in bench["workloads"] if w["name"] == name)
    devices = device_check(entry["chips"], require_tpu)
    dev = devices[0]
    peaks = peaks_for(dev.device_kind, require_tpu)
    # the cache lives at one fixed path inside the checkout (the path is part
    # of its key), whatever the environment names, and evicts nothing: every
    # program a cell compiles stays for the cell's next run
    jax.config.update("jax_compilation_cache_dir",
                      str(HERE.parent / ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    say(f"compile_cache: {jax.config.jax_compilation_cache_dir}")

    traffic = load_module(HERE / "traffic" / f"{workload['traffic']}.py")
    spans = common.Spans(annotate=trace)
    cell = Cell(name=name, seed=seed, params=workload["traffic_params"],
                spec=workload["config_spec"], limits=workload["limits"],
                spans=spans, say=say)
    compiles = common.CompileCounter()
    state = traffic.setup(cell)
    setup_s = time.perf_counter() - (T_START if t_start is None else t_start)
    say(f"setup_s: {setup_s!r}")
    say(common.memory_line("set-up"))

    spans.clear()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    compiles.on = True
    if trace:
        tracing.start(trace_dir)
    try:
        with spans.span("window"):
            result = traffic.window(cell, state, seconds)
    finally:
        if trace:
            tracing.stop()
        compiles.on = False
    say(f"compiles_in_window: {compiles.count}")
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    say(common.memory_line("window"))
    window_spans = list(spans.items)

    traced = None
    if trace:
        t0 = time.perf_counter()
        traced = tracing.reduce(tracing.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        say(f"trace: busy_s={traced['busy_s']!r} window_s="
            f"{traced['window_s']!r} read in {time.perf_counter() - t0!r} s")

    traffic.release(cell, state)
    readings = traffic.readings(cell, state)
    checks = {k: {"value": v, "limit": cell.limits[k]}
              for k, v in readings.items()}
    correct = all(c["limit"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())

    metrics: Dict[str, Dict[str, Any]] = {}
    if not trace:
        values = dict(result["end_to_end"], setup_s=setup_s)
        for m in cell_metrics(bench, name, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        run = dict(result=result, spans=window_spans, trace=traced,
                   params=cell.params, spec=cell.spec, peaks=peaks)
        for m in cell_metrics(bench, name, "per_layer"):
            reader = load_module(HERE / "metrics" / f"{m['name']}.py")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    out: Dict[str, Any] = {"correct": correct,
                           "attempted": result["attempted"],
                           "failed": result["failed"], "metrics": metrics,
                           "device": device}
    if traced is not None:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        out["breakdown"] = traced["breakdown"]
    out["checks"] = checks
    say(f"peak_host_rss_bytes: {common.peak_host_rss_bytes()}")
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return out


def main(argv: Optional[List[str]] = None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(common.load_workload(args.workload), seed=args.seed,
                       seconds=args.seconds, trace=bool(args.trace),
                       require_tpu=require_tpu)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
