"""chip_smoke.py on the CPU: its phases end to end at ``.reduced()`` width
(kernels in interpret mode), its checks, and its refusal to run without a
TPU."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.train import GIB, arch_config

ROOT = Path(__file__).resolve().parents[1]
STEPS, BATCH, SEQ = 2, 2, 32


def _reduced_kernel_cfgs(smoke):
    return {k: get_arch(a).reduced() for k, a in smoke.KERNEL_ARCHS.items()}


def test_phases_end_to_end_at_reduced_width(smoke, capsys):
    cfg = arch_config(smoke.ARCH)
    smoke.phase_device()
    mnt = smoke.phase_data(cfg, seed=0, disk_capacity=GIB)
    trainer, saved = smoke.phase_train(cfg, mnt, steps=STEPS, batch=BATCH,
                                       seq=SEQ, seed=0)
    trainer = smoke.phase_resume(cfg, mnt, trainer, saved, steps=STEPS,
                                 batch=BATCH, seq=SEQ, seed=0)
    assert trainer.step == STEPS
    done = smoke.phase_serve(cfg, trainer.params, n_requests=3, batch=2,
                             min_prompt=4, max_prompt=12, max_new=3, seed=0)
    assert len(done) == 3
    kernels = smoke.phase_kernels(_reduced_kernel_cfgs(smoke), batch=1,
                                  seq=64, checksum_words=5000, seed=0)
    assert set(kernels) == {"flash_attention", "wkv6", "mamba2_ssd",
                            "checksum"}
    assert all(r["ok"] for r in kernels.values())
    # on the CPU the kernels run in interpret mode, never as Mosaic calls
    assert not any(r["compiled"] for r in kernels.values())
    out = capsys.readouterr().out
    assert out.count("train step ") == STEPS
    assert "bit for bit" in out and out.count("serve request ") == 3


def test_resume_check_catches_a_changed_leaf(smoke):
    cfg = arch_config(smoke.ARCH)
    mnt = smoke.phase_data(cfg, seed=1, disk_capacity=GIB)
    trainer, saved = smoke.phase_train(cfg, mnt, steps=1, batch=BATCH,
                                       seq=SEQ, seed=1)
    leaf = saved["params"]["emb"]["ln_f"].copy()
    leaf[0] = np.nextafter(leaf[0], np.float32(np.inf))   # one ulp
    saved["params"]["emb"]["ln_f"] = leaf
    with pytest.raises(smoke.SmokeFailure, match="bits differ"):
        smoke.phase_resume(cfg, mnt, trainer, saved, steps=1, batch=BATCH,
                           seq=SEQ, seed=1)


def test_kernel_check_fails_beyond_tolerance(smoke, monkeypatch):
    real = smoke.ops.wkv6
    monkeypatch.setattr(smoke.ops, "wkv6",
                        lambda *a, **kw: real(*a, **kw) + 0.1)
    monkeypatch.setattr(smoke, "_is_pallas_call", lambda *a, **kw: False)
    with pytest.raises(smoke.SmokeFailure, match="wkv6"):
        smoke.phase_kernels(_reduced_kernel_cfgs(smoke), batch=1, seq=64,
                            checksum_words=2048, seed=0)


def test_main_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=ROOT)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert '"ok"' not in res.stdout
