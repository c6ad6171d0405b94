"""A wave of ``BatchServer.serve`` as a share of the chip's bf16 peak: the
wave's model FLOPs (``flops/moe_mla.py``, the routed experts counted by
the ``expert_rows`` the program's ``server.wave`` spans carry) over the
spans' seconds, over the peak of the ``device_kind`` in ``peaks.json``.
Each wave is one prefill of ``slots`` prompts of ``prompt_len`` tokens
(``max_new`` 1); a program whose spans carry no ``expert_rows`` reports
nothing."""

import importlib.util
from pathlib import Path

from chipbench.program_spans import totals


def read(run):
    t, peaks, p = totals(run).get("server.wave"), run["peaks"], run["params"]
    if not peaks or not t or "expert_rows" not in t or p["max_new"] != 1:
        return None
    path = Path(__file__).resolve().parent.parent / "flops" / "moe_mla.py"
    spec = importlib.util.spec_from_file_location("flops_moe_mla", path)
    flops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flops)
    done = flops.prefill_flops(run["spec"], t["count"] * p["slots"],
                               p["prompt_len"], t["expert_rows"])
    return 100.0 * done / t["seconds"] / peaks["bf16_flops_per_s"]
