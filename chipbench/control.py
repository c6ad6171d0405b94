#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's and the
control's, on many seeds in one process.

    python chipbench/control.py --workload <cell> --seeds 1 2 3 [--no-control]

For each seed it drives the cell's timed path as far as the comparison
needs (the traffic module's ``probe``), and prints one JSON line with the
numbers ``correct`` compares: the program's, and with the control in the
program's place, the control's.  The control is the plain reference in the
next precision below the configuration's (the traffic module says which).
Runs on the chip; the benchmark's own runs do not run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE.parent))

from chipbench import common, run  # noqa: E402


def readings(workload, seeds, control: bool = True, require_tpu: bool = True):
    import jax
    dev = run.device_check(1, require_tpu)[0]
    jax.config.update("jax_compilation_cache_dir",
                      str(HERE.parent / ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    traffic = run.load_module(HERE / "traffic" / f"{workload['traffic']}.py")
    for seed in seeds:
        cell = run.Cell(name=workload["name"], seed=seed,
                        params=workload["traffic_params"],
                        spec=workload["config_spec"],
                        limits=workload["limits"], spans=common.Spans(),
                        say=run.say)
        t0 = time.perf_counter()
        st = traffic.probe(cell)
        out = {"seed": seed, "device": dev.device_kind,
               "program": traffic.readings(cell, st)}
        if control:
            out["control"] = traffic.readings(cell, st, control=True)
        out["seconds"] = time.perf_counter() - t0
        yield out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args()
    for out in readings(common.load_workload(args.workload), args.seeds,
                        control=not args.no_control):
        print("READING " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
