"""An expert-parallel serving rank of a hybrid model (KDA beside latent
attention) that starts cold from its share of the weights stored in CFS.

The cold start of ``cold_start.py``: its set-up, window, ``SpanMount``,
prompts and probe, taken from that file into a module of this cell's own
(as ``cold_start_moe.py`` does), in which the weights and the program's
configuration are this cell's.  The weights are the share that
``reference/kimi_linear.py`` makes from the seed (the held experts, KDA
and latent attention, router, shared expert, dense layer, embedding and
head), and the configuration adds the KDA, latent-attention and
expert-share sizes of the configuration file to ``common.arch_config``'s.

``correct`` compares, once the window has closed, with
``reference/kimi_linear.py`` as the plain float32 reference:
  * each restored leaf's fingerprint with the saved leaf's (exact);
  * every served token with the reference's logits after the same prompt:
    the mean, over the window's served tokens, of the gap by which a
    served token's logit lies below the reference's best (the mean, for
    the reason ``cold_start_moe.py`` gives: bfloat16 flips some of the
    top-8-of-256 choices that float32 makes).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import types
from pathlib import Path
from typing import Any, Dict

import numpy as np

from chipbench import common

# configuration file keys -> the program's ArchConfig fields, beyond
# those of ``common.arch_config``
_KDA_KEYS = {
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "mla_use_nope": "mla_nope",
    "num_experts": "n_experts",
    "num_experts_per_token": "top_k",
    "moe_intermediate_size": "d_expert",
    "num_shared_experts": "n_shared_experts",
    "first_k_dense_replace": "first_k_dense",
    "routed_scaling_factor": "routed_scale",
    "num_experts_held": "n_experts_held",
    "moe_router_activation_func": "router",
    "rms_norm_eps": "norm_eps",
    "kda_gate_rank": "kda_gate_rank",
    "kda_chunk_size": "kda_chunk",
}
# ``linear_attn_config`` keys -> ArchConfig fields
_LINEAR_KEYS = {
    "num_heads": "kda_heads",
    "head_dim": "kda_head_dim",
    "short_conv_kernel_size": "kda_conv",
}


def arch_config(spec: Dict[str, Any]):
    """The program's ArchConfig for the share the file describes."""
    from chipbench.reference import kimi_linear
    dm = kimi_linear.Dims.of(spec)    # refuses a block it does not compute
    lin = spec["linear_attn_config"]
    sizes = {field: spec[key] for key, field in _KDA_KEYS.items()}
    sizes.update({field: lin[key] for key, field in _LINEAR_KEYS.items()})
    return dataclasses.replace(common.arch_config(spec), expert_lo=dm.lo,
                               kda_layers=tuple(lin["kda_layers"]), **sizes)


def _weights(cell):
    import jax

    from chipbench.reference import kimi_linear
    dm = kimi_linear.Dims.of(cell.spec)
    return jax.jit(kimi_linear.serve_weights, static_argnums=(0, 1))(
        dm, cell.seed)


def _cold_start():
    """``cold_start.py`` as a module of this cell's own, its weights and
    configuration this cell's."""
    path = Path(__file__).with_name("cold_start.py")
    spec = importlib.util.spec_from_file_location("chipbench_cold_start_kda_base",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.common = types.ModuleType("chipbench_common_kda")
    mod.common.__dict__.update(common.__dict__, arch_config=arch_config)
    mod._weights = _weights
    return mod


_base = _cold_start()
SpanMount, prompts, fp8 = _base.SpanMount, _base.prompts, _base.fp8
setup, window, probe = _base.setup, _base.window, _base.probe
server_for, release = _base.server_for, _base.release


def readings(cell, st: Dict[str, Any], control: bool = False
             ) -> Dict[str, float]:
    """The numbers ``correct`` compares.  With ``control`` the reference
    with float8 weights takes the server's place: its first choice at each
    prompt stands for the served token."""
    import jax

    from chipbench.reference import kimi_linear

    p = cell.params
    dm = kimi_linear.Dims.of(cell.spec)
    bad_leaves = sum(int(np.any(s["fp"] != st["saved_fp"]))
                     for s in st["served"])
    w = _weights(cell)
    gaps, missing = [], 0
    for s in st["served"]:
        toks = prompts(p, dm.vocab, cell.seed, s["k"])
        with jax.default_matmul_precision("highest"):
            want = np.asarray(kimi_linear.last_logits(dm, w, toks))
        if control:
            got = np.asarray(kimi_linear.last_logits(dm, w, toks,
                                                     quantize=fp8))
            first = np.argmax(got[:, :dm.vocab], -1)
        else:
            first = []
            for i in range(len(toks)):
                out = s["out"].get(i, [])
                if not out or not 0 <= out[0] < dm.vocab:
                    missing += 1
                    first.append(int(np.argmin(want[i, :dm.vocab])))
                else:
                    first.append(out[0])
        best = want[:, :dm.vocab].max(-1)
        gaps.extend(best - want[np.arange(len(toks)), first])
    common.delete_tree(w)
    q = np.quantile(gaps, [0.5, 0.9, 1.0])
    cell.say(f"reference: {len(st['served'])} waves compared; gaps of "
             f"{len(gaps)} tokens: mean {np.mean(gaps)!r} median {q[0]!r} "
             f"p90 {q[1]!r} widest {q[2]!r}; "
             f"{int(np.sum(np.equal(gaps, 0)))} the reference's best")
    return {"weights_wrong": float(bad_leaves), "answers_missing":
            float(missing), "token_gap_mean": float(np.mean(gaps))}
