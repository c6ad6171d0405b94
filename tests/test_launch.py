"""Launchers: the injected-crash resume, device errors that must not be
taken for one, and the compile-cache helper."""

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache, serve, train
from repro.train import trainer as trainer_mod


@pytest.fixture
def cache_config(tmp_path):
    """Restore JAX's compile-cache settings after the test; compiles made
    during it are not cached."""
    saved_dir = jax.config.jax_compilation_cache_dir
    saved_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "preset"))
    try:
        yield tmp_path / "preset"
    finally:
        jax.config.update("jax_compilation_cache_dir", saved_dir)
        jax.config.update("jax_enable_compilation_cache", saved_on)
        compilation_cache.reset_cache()


def test_cache_helper_keeps_a_preset_dir(cache_config):
    assert compile_cache.enable_compile_cache() == str(cache_config)
    assert jax.config.jax_compilation_cache_dir == str(cache_config)


def test_cache_helper_sets_the_fixed_repo_path(cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.REPO_CACHE_DIR)
    assert compile_cache.REPO_CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.REPO_CACHE_DIR.parent / "chip_smoke.py").exists()


def test_injected_crash_resumes(cache_config, capsys):
    train.main(["--steps", "4", "--ckpt-every", "2", "--crash-at", "3",
                "--seq", "16"])
    out = capsys.readouterr().out
    assert "resuming from CFS checkpoint" in out
    assert "resumed at step 2" in out


def test_device_error_in_a_step_propagates(cache_config, capsys,
                                           monkeypatch):
    def failing_step(cfg, oc):
        def step(params, opt_state, batch):
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: out of memory in the train step")
        return step

    monkeypatch.setattr(trainer_mod, "make_train_step", failing_step)
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE_EXHAUSTED"):
        train.main(["--steps", "3", "--seq", "16"])
    assert "resum" not in capsys.readouterr().out


def test_serve_launcher_answers_every_request(cache_config, capsys):
    serve.main(["--arch", "minicpm-2b", "--requests", "3", "--max-new", "2"])
    out = capsys.readouterr().out
    assert out.count("req ") == 3
    assert "served 3 requests" in out
