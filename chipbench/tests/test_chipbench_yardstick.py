"""The yardstick's own arithmetic: model FLOPs, the peaks table, the
reference's weights, and the harness's refusals without a chip."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import common
from chipbench.flops import dense as flops
from chipbench.reference import dense
from chipbench.tests.helpers import REDUCED

ROOT = Path(__file__).resolve().parents[2]


def test_dense_flops_match_a_hand_count_at_minicpm_widths():
    spec = common.load_json(ROOT / "chipbench" / "configs"
                            / "minicpm-2b-l2-train.json")
    proj = 2304 * 2304 * 4 + 3 * 2304 * 5760     # q, k, v, o; w1, w3, w2
    attn = 2 * 36 * 64 * (4096 + 1) / 2          # causal q.k and p.v
    head = 2304 * 122753
    forward = 2 * 2 * proj + 2 * 2 * attn + 2 * head
    assert forward == 847_590_912
    assert flops.train_flops_per_token(spec, 4096) == 3 * forward


def test_peaks_are_keyed_by_device_kind_with_their_source():
    peaks = common.load_json(ROOT / "chipbench" / "peaks.json")
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    from chipbench import run
    with pytest.raises(SystemExit):
        run.peaks_for("TPU v9 imaginary", require_tpu=True)


def _spec():
    spec = dict(common.load_workload("minicpm2b-train-stream")["config_spec"])
    spec.update(REDUCED)
    return spec


def test_reference_init_is_the_trainers_init_bit_for_bit():
    """The reference makes its own weights from the seed; they have to be
    the ones the trainer starts from, or no step could be compared."""
    from repro.models import get_model
    spec = _spec()
    cfg = common.arch_config(spec)
    program = get_model(cfg).init(jax.random.PRNGKey(2 ** 31 + 5),
                                  jnp.float32)
    ref = dense.train_init(dense.Dims.of(spec), 2 ** 31 + 5)
    assert common.leaf_names(program) == common.leaf_names(ref)
    for a, b in zip(jax.tree.leaves(program), jax.tree.leaves(ref)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serving_weights_have_the_servers_tree():
    from repro.models import get_model
    spec = _spec()
    cfg = common.arch_config(spec)
    program = jax.eval_shape(lambda: get_model(cfg).init(
        jax.random.PRNGKey(0), jnp.bfloat16))
    ours = jax.eval_shape(lambda: dense.serve_weights(dense.Dims.of(spec),
                                                      0))
    assert jax.tree.structure(program) == jax.tree.structure(ours)
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(program)] == \
        [(x.shape, x.dtype) for x in jax.tree.leaves(ours)]


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "minicpm2b-cold-start", "--seed", str(2 ** 31 + 3), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    r = _run_py(ROOT)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "no TPU" in r.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_py(tmp_path)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
