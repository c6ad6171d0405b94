"""Each traffic module's set-up, a one-second window and its comparison,
through the harness's run with the look for a chip skipped, at the
program's ``.reduced()`` widths on the CPU."""

import time

from chipbench import run
from chipbench.tests.helpers import bench_with, small_workload


def _run(name, trace, seed=2 ** 31 + 11):
    return run.run_cell(small_workload(name), seed=seed, seconds=1.0,
                        trace=trace, require_tpu=False,
                        bench=bench_with(name), t_start=time.perf_counter())


def test_cold_start_cell_runs_and_is_correct():
    out = _run("minicpm2b-cold-start", trace=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"cold_ttft_s", "setup_s"}
    assert list(out)[-1] == "checks"


def test_cold_start_traced_run_reports_its_layers():
    out = _run("minicpm2b-cold-start", trace=True)
    assert out["correct"], out["checks"]
    assert {"restore_ms.cold", "cfs_read_ms.cold",
            "first_wave_ms.cold"} <= set(out["metrics"])
    # the CPU has no TPU plane: busy time is not read from it
    assert out["device"]["busy_s"] == 0.0
    assert out["device"]["window_s"] >= 1.0


def test_train_stream_cell_feeds_the_trainer_the_corpus():
    """The training cell is out of the benchmark (its program's gradient
    is at fault); its harness still has to feed and read the trainer."""
    out = _run("minicpm2b-train-stream", trace=False)
    assert out["checks"]["batches_wrong"]["value"] == 0
    assert out["attempted"] >= 3 and out["failed"] == 0


def test_unset_limit_is_not_correct():
    wl = small_workload("minicpm2b-cold-start")
    wl["limits"]["token_gap"] = None
    out = run.run_cell(wl, seed=3, seconds=0.2, trace=False,
                       require_tpu=False, t_start=time.perf_counter())
    assert not out["correct"]
