"""The Kimi-Linear expert-share cold-start cell at a size a CPU test run
holds: the committed workload and configuration files with the widths cut
(the published expert counts kept: 256 routed, 8 per token, 8 held, 1
shared), 4 layers (the dense KDA layer, two KDA and one latent MoE layer)
and short prompts that cross a KDA chunk edge.  Untraced and traced runs
through the harness are ``correct``; with the KDA layer broken underneath
they are not; the wave's share of the peak reads its FLOPs from
``flops/kda_mla.py``."""

import copy
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import common, run
from chipbench.tests.helpers import bench_with
from repro import obs

CELL = "kimi-linear-ep32-cold-start"
SMALL = {"hidden_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 4, "intermediate_size": 256,
         "vocab_size": 512, "num_hidden_layers": 4, "kv_lora_rank": 64,
         "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
         "moe_intermediate_size": 64, "kda_gate_rank": 32,
         "kda_chunk_size": 16}
# the .cold metrics whose lists name this cell
COLD = {"restore_ms.cold", "cfs_read_ms.cold", "first_wave_ms.cold"}


def small_workload():
    wl = copy.deepcopy(common.load_workload(CELL))
    wl["config_spec"].update(SMALL)
    wl["config_spec"]["linear_attn_config"].update(num_heads=4, head_dim=32)
    wl["traffic_params"].update(slots=4, prompt_len=40)
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
    assert COLD | {"wave_mfu.kda"} <= set(out["metrics"])
    assert "wave_mfu.moe" not in out["metrics"]
    assert 0 < out["metrics"]["wave_mfu.kda"]["value"] < 100


def test_wave_spans_carry_the_expert_rows_and_both_states():
    from chipbench.traffic import cold_start_kda as traffic
    wl = small_workload()
    cell = run.Cell(name=CELL, seed=5, params=wl["traffic_params"],
                    spec=wl["config_spec"], limits=wl["limits"],
                    spans=common.Spans(), say=lambda line: None)
    cfg = traffic.arch_config(cell.spec)
    assert (cfg.n_experts, cfg.n_experts_held, cfg.expert_lo, cfg.top_k,
            cfg.router, cfg.mla_nope) == (256, 8, 0, 8, "sigmoid", True)
    assert cfg.layer_kinds() == ["kda", "kda", "kda", "attn"]
    srv = traffic.server_for(cfg, traffic._weights(cell), cell.params)
    with obs.recording() as rec:
        srv.serve(traffic._base._requests(
            cell.params, traffic.prompts(cell.params, cfg.vocab, 5, 0)))
    wave = obs.totals(rec.spans)["server.wave"]
    # 4 prompts x 40 tokens x 8 choices over 3 MoE layers, 8 of 256 held
    assert 0 < wave["expert_rows_max"] <= wave["expert_rows"] <= 4 * 40 * 8 * 3
    # 3 KDA layers: state [4, 4, 32, 32] float32, conv tail [4, 3, 384]
    # bfloat16; 1 latent layer: [4, 41, 80] bfloat16
    assert wave["state_bytes"] == 3 * (4 * 4 * 32 * 32 * 4 + 4 * 3 * 384 * 2)
    assert wave["cache_bytes"] == 4 * 41 * 80 * 2


# ----------------------------------------------------------------- faults

def _no_delta(q, k, v, g, b, state, chunk=64):
    """Gated linear attention: the decay and the write, no delta
    correction."""
    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = (jnp.exp(g_t)[..., None] * S
             + jnp.einsum("bhk,bhv->bhkv", b_t[..., None] * k_t, v_t))
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, b))
    state, o = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 1), state


@pytest.mark.parametrize("fault", ["no-delta", "no-decay"])
def test_broken_kda_layer_is_caught(monkeypatch, fault):
    from repro.kernels import ref
    chunked = ref.kda_chunked

    def no_decay(q, k, v, g, b, state, chunk=64):
        return chunked(q, k, v, jnp.zeros_like(g), b, state, chunk)

    monkeypatch.setattr(ref, "kda_chunked",
                        _no_delta if fault == "no-delta" else no_decay)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["token_gap_mean"]["value"] > \
        out["checks"]["token_gap_mean"]["limit"]


# ----------------------------------------------------------------- FLOPs

def test_flops_match_a_hand_count_at_published_widths():
    from chipbench.flops import kda_mla as flops
    spec = common.load_workload(CELL)["config_spec"]
    d, hk = 2304, 32 * 128
    kda = 2304 * 3 * hk + 2304 * 32 + hk * 2304 + 2 * (2304 * 128 + 128 * hk)
    # per chunk of 64 and head: q.k and k.k lower triangles, the solve,
    # W S, (q exp G) S, the state update, M U
    scan = 32 * 2 * (64 * 64 * 128 + 64 * 64 * 256 / 2 + 3 * 64 * 128 * 128
                     + 64 * 64 * 128 / 2) / 64
    assert flops.kda_scan_flops(spec) == scan
    mla = d * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d
    attn = 32 * (192 + 128) * (4096 + 1) / 2
    per_token = (2 * (15 * kda + 5 * (mla + attn) + 3 * d * 9216
                      + 19 * (d * 256 + 3 * d * 1024)) + 15 * scan)
    assert flops.token_flops(spec, 4096) == per_token
    assert flops.expert_row_flops(spec) == 2 * 3 * d * 1024
    head = 2 * d * 163840
    assert flops.prefill_flops(spec, 8, 4096, 100) == \
        8 * (4096 * per_token + head) + 100 * 2 * 3 * d * 1024
    assert flops.kda_flops(spec, 8, 4096) == 15 * 8 * 4096 * scan
    assert flops.kda_bytes(spec, 8, 4096) == \
        15 * 8 * 4 * (4096 * (5 * hk + 32) + 32 * 128 * 128)


def test_wave_share_on_spans_by_hand():
    """Two waves of 1.5 s at published widths, 155,648 rows each: the
    share is their FLOPs over three seconds over the v5e's 197 TFLOP/s."""
    from chipbench.flops import kda_mla as flops
    wl = common.load_workload(CELL)
    spans = []
    for k in range(2):
        s = obs.Span(None, "server.wave",
                     {"slots": 8, "tokens": 8, "expert_rows": 155_648,
                      "expert_rows_max": 2_000, "state_bytes": 1,
                      "cache_bytes": 1})
        s.id, s.parent, s.start, s.end = k + 1, None, 20.0 * k, 20.0 * k + 1.5
        spans.append(s)
    reader = run.load_module(run.HERE / "metrics" / "wave_mfu.kda.py")
    peaks = common.load_json(run.HERE / "peaks.json")["devices"]
    runs = {"result": {"cold_starts": 2}, "spans": [],
            "program_spans": spans, "spec": wl["config_spec"],
            "params": wl["traffic_params"], "peaks": peaks["TPU v5 lite"]}
    want = 100 * flops.prefill_flops(wl["config_spec"], 16, 4096,
                                     2 * 155_648) / 3.0 / 197e12
    assert reader.read(runs) == pytest.approx(want)
    for s in spans:
        del s.counts["expert_rows"]        # a program without the counts
    assert reader.read(runs) is None
