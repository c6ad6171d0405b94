"""Benchmark driver — one suite per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--suite mdtest|largefile|smallfile|expansion|hotset|dataloader|qos]
                                            [--smoke]

Prints CSV rows (test,system,clients,procs,ops,sim_iops,...,p99_us,...),
writes results/bench/<suite>.csv, and drops a machine-readable perf
trajectory BENCH_<suite>.json at the repo root (simulated-time fields only,
so same-seed reruns are bit-identical — see EXPERIMENTS.md for the schema).
``--smoke`` shrinks every sweep to a <30 s run for CI drift detection; the
largefile smoke includes the read-path A/B rows (SeqRead with a nonzero
CFS_READ_WINDOW, RandRead with an injected straggler replica), so the
windowed-read and hedge paths are exercised on every push.  Smoke output
goes to side paths (results/bench/*.smoke.csv, BENCH_*.smoke.json under
results/bench/) and never clobbers the committed full-sweep baselines."""

from __future__ import annotations

import argparse
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
RESULTS = ROOT / "results" / "bench"


def run_suite(name: str, rows: list, smoke: bool) -> list:
    from . import (dataloader, expansion, hotset, largefile, mdtest, qos,
                   smallfile)
    mod = {"mdtest": mdtest, "largefile": largefile,
           "smallfile": smallfile, "expansion": expansion,
           "hotset": hotset, "dataloader": dataloader, "qos": qos}[name]
    return mod.run(rows, smoke=smoke)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default="all",
                    choices=["all", "mdtest", "largefile", "smallfile",
                             "expansion", "hotset", "dataloader", "qos"])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny op counts (<30 s total) for CI drift checks")
    args = ap.parse_args()
    RESULTS.mkdir(parents=True, exist_ok=True)

    suites = (["mdtest", "largefile", "smallfile", "expansion", "hotset",
               "dataloader", "qos"]
              if args.suite == "all" else [args.suite])
    from .common import HEADER
    for suite in suites:
        print(f"=== suite: {suite} ===")
        rows: list = [HEADER]
        json_results = run_suite(suite, rows, args.smoke)
        for row in rows:
            print(row)
        # smoke runs go to a side path: they must never clobber the
        # committed full-sweep baselines (csv + BENCH_*.json)
        suffix = ".smoke.csv" if args.smoke else ".csv"
        (RESULTS / f"{suite}{suffix}").write_text("\n".join(rows) + "\n")
        payload = {"suite": suite, "smoke": args.smoke,
                   "results": json_results}
        name = f"BENCH_{suite}.smoke.json" if args.smoke else f"BENCH_{suite}.json"
        out = (RESULTS if args.smoke else ROOT) / name
        out.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")


if __name__ == "__main__":
    main()
