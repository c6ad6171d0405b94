"""An expert-parallel serving rank that starts cold from its share of the
weights stored in CFS.

The cold start of ``cold_start.py``: its set-up, window, ``SpanMount``,
prompts and probe, taken from that file into a module of this cell's own,
in which the weights and the program's configuration are this cell's.  The
weights are the share that ``reference/moonlight.py`` makes from the seed
(the held experts, attention, router, shared experts, dense layers,
embedding and head), and the configuration adds the latent-attention and
expert-share sizes of the configuration file to ``common.arch_config``'s.

``correct`` compares, once the window has closed, with
``reference/moonlight.py`` as the plain float32 reference:
  * each restored leaf's fingerprint with the saved leaf's (exact);
  * every served token with the reference's logits after the same prompt:
    the mean, over the window's served tokens, of the gap by which a
    served token's logit lies below the reference's best.

The mean and not the widest gap (``cold_start.py``'s): a bfloat16 forward
flips some of the 20 layers' top-6 choices that the float32 one makes (a
score within rounding of the 6th), and each flip swaps a whole expert's
part of the token's output, so a minority of prompts end far from the
reference, in the program as in the reference itself run in bfloat16.
Their widest gaps reach the float8 control's, which errs on most prompts;
the mean over all of them separates the two (``PERF.md``).
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
_MOE_KEYS = {
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "n_routed_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "d_expert",
    "n_shared_experts": "n_shared_experts",
    "first_k_dense_replace": "first_k_dense",
    "routed_scaling_factor": "routed_scale",
    "n_routed_experts_held": "n_experts_held",
    "scoring_func": "router",
    "rms_norm_eps": "norm_eps",
}


def arch_config(spec: Dict[str, Any]):
    """The program's ArchConfig for the share the file describes."""
    from chipbench.reference import moonlight
    dm = moonlight.Dims.of(spec)      # refuses a block it does not compute
    sizes = {field: spec[key] for key, field in _MOE_KEYS.items()}
    return dataclasses.replace(common.arch_config(spec), expert_lo=dm.lo,
                               **sizes)


def _weights(cell):
    import jax

    from chipbench.reference import moonlight
    dm = moonlight.Dims.of(cell.spec)
    return jax.jit(moonlight.serve_weights, static_argnums=(0, 1))(
        dm, cell.seed)


def _cold_start():
    """``cold_start.py`` as a module of this cell's own, its weights and
    configuration this cell's."""
    path = Path(__file__).with_name("cold_start.py")
    spec = importlib.util.spec_from_file_location("chipbench_cold_start_moe_base",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.common = types.ModuleType("chipbench_common_moe")
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

    from chipbench.reference import moonlight

    p = cell.params
    dm = moonlight.Dims.of(cell.spec)
    bad_leaves = sum(int(np.any(s["fp"] != st["saved_fp"]))
                     for s in st["served"])
    w = _weights(cell)
    gaps, missing = [], 0
    for s in st["served"]:
        toks = prompts(p, dm.vocab, cell.seed, s["k"])
        with jax.default_matmul_precision("highest"):
            want = np.asarray(moonlight.last_logits(dm, w, toks))
        if control:
            got = np.asarray(moonlight.last_logits(dm, w, toks,
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
