"""The whole training step's share of the chip's bf16 peak: model FLOPs per
token (``flops/<family>.py``; forward and backward, recompute not counted)
times the traced window's tokens per second, over the peak of the
``device_kind`` in ``peaks.json``."""

from pathlib import Path


def read(run):
    res, peaks = run["result"], run["peaks"]
    if not peaks or not res.get("tokens") or not res.get("elapsed_s"):
        return None
    import importlib.util
    path = (Path(__file__).resolve().parent.parent / "flops"
            / f"{run['spec']['family']}.py")
    spec = importlib.util.spec_from_file_location("flops_family", path)
    flops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flops)
    per_token = flops.train_flops_per_token(run["spec"], run["params"]["seq"])
    rate = res["tokens"] / res["elapsed_s"]
    return 100.0 * per_token * rate / peaks["bf16_flops_per_s"]
