"""The Moonlight expert-share cold-start cell at a size a CPU test run
holds: the committed workload and configuration files with the widths cut
(the published expert counts kept: 64 routed, 6 per token, 8 held, 2
shared), 3 layers (the dense one and two MoE layers) and short prompts.
Untraced and traced runs through the harness are ``correct``; with the
timed path broken underneath they are not; the wave's share of the peak
reads its FLOPs from ``flops/moe_mla.py``."""

import copy
import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common, run
from chipbench.tests.helpers import bench_with
from repro import obs

CELL = "moonlight-ep8-cold-start"
SMALL = {"hidden_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 4, "intermediate_size": 256,
         "vocab_size": 512, "num_hidden_layers": 3, "kv_lora_rank": 64,
         "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
         "moe_intermediate_size": 64}
# the .cold metrics whose lists name this cell
COLD = {"restore_ms.cold", "cfs_read_ms.cold", "first_wave_ms.cold"}


def small_workload():
    wl = copy.deepcopy(common.load_workload(CELL))
    wl["config_spec"].update(SMALL)
    wl["traffic_params"].update(slots=4, prompt_len=16)
    return wl


def _run(trace=False, seed=2 ** 31 + 13, seconds=0.5):
    return run.run_cell(small_workload(), seed=seed, seconds=seconds,
                        trace=trace, require_tpu=False, bench=bench_with(CELL),
                        t_start=time.perf_counter())


def test_cell_runs_and_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 4 and out["failed"] == 0
    assert set(out["metrics"]) == {"cold_ttft_s", "setup_s"}


def test_traced_run_reports_the_cold_layers_and_the_wave_share(monkeypatch):
    peaks = common.load_json(run.HERE / "peaks.json")["devices"]
    monkeypatch.setattr(run, "peaks_for",
                        lambda kind, require: peaks["TPU v5 lite"])
    out = _run(trace=True)
    assert out["correct"], out["checks"]
    assert COLD | {"wave_mfu.moe"} <= set(out["metrics"])
    assert 0 < out["metrics"]["wave_mfu.moe"]["value"] < 100


def test_wave_spans_carry_the_expert_rows():
    from chipbench.traffic import cold_start_moe as traffic
    wl = small_workload()
    cell = run.Cell(name=CELL, seed=5, params=wl["traffic_params"],
                    spec=wl["config_spec"], limits=wl["limits"],
                    spans=common.Spans(), say=lambda line: None)
    cfg = traffic.arch_config(cell.spec)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.expert_lo, cfg.top_k,
            cfg.router) == (64, 8, 0, 6, "sigmoid")
    srv = traffic.server_for(cfg, traffic._weights(cell), cell.params)
    with obs.recording() as rec:
        srv.serve(traffic._base._requests(
            cell.params, traffic.prompts(cell.params, cfg.vocab, 5, 0)))
    wave = obs.totals(rec.spans)["server.wave"]
    # 4 prompts x 16 tokens x 6 choices over 2 MoE layers, 8 of 64 held
    assert 0 < wave["expert_rows_max"] <= wave["expert_rows"] <= 4 * 16 * 6 * 2


# ----------------------------------------------------------------- faults

def _patch_share(monkeypatch, broken):
    """The program's MoE layers run ``broken`` in the expert share's
    place."""
    from repro.models import transformer
    monkeypatch.setattr(transformer, "moe_share", broken)


def test_shared_experts_left_out_are_caught(monkeypatch):
    from repro.models import moe

    def no_shared(cfg, p, x):
        return moe.moe_share(cfg, {k: v for k, v in p.items()
                                   if k != "shared"}, x)
    _patch_share(monkeypatch, no_shared)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["token_gap_mean"]["value"] > \
        out["checks"]["token_gap_mean"]["limit"]


def test_tokens_dropped_past_capacity_are_caught(monkeypatch):
    """Routing with the capacity path's capacity: the wave's tokens in 16
    groups (``moe_block``'s), each held expert taking at most 1.25 times
    its even share of a group's assignments; those past it add
    nothing."""
    import jax

    from repro.models import moe
    from repro.models.layers import swiglu

    def capped(cfg, p, x):
        xf = x.reshape(-1, x.shape[-1])
        top_e, top_w = moe.route_topk(cfg, p, xf)
        n, k = top_e.shape
        groups = 16
        hot = jax.nn.one_hot(top_e.reshape(groups, -1), cfg.n_experts,
                             dtype=jnp.int32)
        seat = jnp.sum(jnp.cumsum(hot, 1) * hot, -1).reshape(n, k)
        cap = int(n // groups * k / cfg.n_experts * 1.25) + 1
        out, counts = moe.held_experts(cfg, p, xf, top_e,
                                       jnp.where(seat <= cap, top_w, 0.0))
        return out.reshape(x.shape) + swiglu(p["shared"], x), counts
    _patch_share(monkeypatch, capped)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["token_gap_mean"]["value"] > \
        out["checks"]["token_gap_mean"]["limit"]


def test_flipped_bit_in_a_restored_expert_is_caught(monkeypatch):
    from repro.storage import checkpoint
    restore = checkpoint.CheckpointManager.restore

    def flipped(self, like, step=None, put=lambda a: a):
        def put_one(arr):
            if arr.ndim == 4:            # [layers, held, d, f]: an expert
                arr = arr.copy()
                arr.view(np.uint16).flat[7] ^= 1 << 3
            return put(arr)
        return restore(self, like, step, put_one)

    monkeypatch.setattr(checkpoint.CheckpointManager, "restore", flipped)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["weights_wrong"]["value"] > 0


# ----------------------------------------------------------------- FLOPs

def test_flops_match_a_hand_count_at_published_widths():
    from chipbench.flops import moe_mla as flops
    spec = common.load_workload(CELL)["config_spec"]
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    attn = 16 * (192 + 128) * (1024 + 1) / 2
    per_token = 2 * (21 * (mla + attn) + 3 * 2048 * 11264
                     + 20 * (2048 * 64 + 3 * 2048 * 2816))
    assert flops.token_flops(spec, 1024) == per_token
    assert flops.expert_row_flops(spec) == 2 * 3 * 2048 * 1408
    head = 2 * 2048 * 163840
    assert flops.prefill_flops(spec, 32, 1024, 100) == \
        32 * (1024 * per_token + head) + 100 * 2 * 3 * 2048 * 1408


def test_wave_share_on_spans_by_hand():
    """Two waves of 0.5 s at published widths, 491,520 rows each: the
    share is their FLOPs over one second over the v5e's 197 TFLOP/s."""
    from chipbench.flops import moe_mla as flops
    wl = common.load_workload(CELL)
    spans = []
    for k in range(2):
        s = obs.Span(None, "server.wave",
                     {"slots": 32, "tokens": 32, "expert_rows": 491_520,
                      "expert_rows_max": 3_200})
        s.id, s.parent, s.start, s.end = k + 1, None, 10.0 * k, 10.0 * k + .5
        spans.append(s)
    reader = run.load_module(run.HERE / "metrics" / "wave_mfu.moe.py")
    peaks = common.load_json(run.HERE / "peaks.json")["devices"]
    runs = {"result": {"cold_starts": 2}, "spans": [],
            "program_spans": spans, "spec": wl["config_spec"],
            "params": wl["traffic_params"], "peaks": peaks["TPU v5 lite"]}
    want = 100 * flops.prefill_flops(wl["config_spec"], 64, 1024,
                                     2 * 491_520) / 1.0 / 197e12
    assert reader.read(runs) == pytest.approx(want)
    for s in spans:
        del s.counts["expert_rows"]        # a program without the counts
    assert reader.read(runs) is None
