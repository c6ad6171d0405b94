"""Cells at a size a CPU test run holds: the committed workload files with
the widths cut to the program's ``.reduced()`` sizes and small traffic."""

from __future__ import annotations

import copy
from typing import Any, Dict

from chipbench import common

REDUCED = {"hidden_size": 128, "num_attention_heads": 4,
           "num_key_value_heads": 4, "head_dim": 32,
           "intermediate_size": 256, "vocab_size": 512,
           "num_hidden_layers": 2}

SMALL_TRAFFIC = {
    "minicpm2b-train-stream": {"shards": 2, "tokens_per_shard": 4096,
                               "batch": 2, "seq": 64, "doc_max": 512},
    "minicpm2b-cold-start": {"slots": 2, "prompt_len": 16},
}


def bench_with(name: str) -> Dict[str, Any]:
    """BENCHMARK.json, with ``name`` as a one-chip cell if it is not one."""
    bench = copy.deepcopy(common.load_benchmark())
    if not any(w["name"] == name for w in bench["workloads"]):
        bench["workloads"].append({"name": name, "chips": 1})
    return bench


def small_workload(name: str) -> Dict[str, Any]:
    wl = copy.deepcopy(common.load_workload(name))
    wl["config_spec"].update(REDUCED)
    wl["traffic_params"].update(SMALL_TRAFFIC[name])
    return wl
