"""With the timed path broken underneath, a run's ``correct`` comes out
false: the cold-start cell's answer altered where the server produces it,
half of its wave left unanswered, and a restored weight altered where the
checkpoint hands it over.  The
training cell's faults: a step that returns its state unchanged, and one
that takes the mean over half the batch."""

import time

import jax.numpy as jnp

from chipbench import run
from chipbench.tests.helpers import bench_with, small_workload


def _run(name, seed=17):
    return run.run_cell(small_workload(name), seed=seed, seconds=0.5,
                        trace=False, require_tpu=False,
                        bench=bench_with(name), t_start=time.perf_counter())


def test_altered_token_is_caught(monkeypatch):
    from repro.serve import server
    serve = server.BatchServer.serve

    def altered(self, requests):
        done = serve(self, requests)
        done[0].out[0] = (done[0].out[0] + 1) % self.cfg.vocab
        return done

    monkeypatch.setattr(server.BatchServer, "serve", altered)
    out = _run("minicpm2b-cold-start")
    assert not out["correct"]
    assert out["checks"]["token_gap"]["value"] > \
        out["checks"]["token_gap"]["limit"]


def test_half_the_wave_left_out_is_caught(monkeypatch):
    from repro.serve import server
    serve = server.BatchServer.serve

    def half(self, requests):
        return serve(self, requests[: len(requests) // 2])

    monkeypatch.setattr(server.BatchServer, "serve", half)
    out = _run("minicpm2b-cold-start")
    assert not out["correct"]
    assert out["failed"] > 0
    assert out["checks"]["answers_missing"]["value"] > 0


def test_altered_weight_is_caught(monkeypatch):
    from repro.storage import checkpoint
    restore = checkpoint.CheckpointManager.restore

    def altered(self, like, step=None, put=lambda a: a):
        def put_one(arr):
            if arr.ndim == 1:
                arr = arr.copy()
                arr[0] = arr[0] * 2
            return put(arr)
        return restore(self, like, step, put_one)

    monkeypatch.setattr(checkpoint.CheckpointManager, "restore", altered)
    out = _run("minicpm2b-cold-start")
    assert not out["correct"]
    assert out["checks"]["weights_wrong"]["value"] > 0


def _broken_step(monkeypatch, broken):
    from repro.train import trainer
    make = trainer.jit_train_step

    def patched(cfg, oc):
        return broken(make(cfg, oc))

    monkeypatch.setattr(trainer, "jit_train_step", patched)


def test_step_that_keeps_its_state_is_caught(monkeypatch):
    def broken(step):
        def same(params, opt_state, batch):
            _, _, metrics = step(jax_copy(params), jax_copy(opt_state), batch)
            return params, opt_state, metrics
        return same
    _broken_step(monkeypatch, broken)
    out = _run("minicpm2b-train-stream")
    assert out["checks"]["update_gap"]["value"] > 0.9


def test_half_batch_is_caught(monkeypatch):
    def broken(step):
        def half(params, opt_state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(params, opt_state,
                        {k: v[:n] for k, v in batch.items()})
        return half
    _broken_step(monkeypatch, broken)
    out = _run("minicpm2b-train-stream")
    assert out["checks"]["loss_gap"]["value"] > 1e-2


def jax_copy(tree):
    import jax
    return jax.tree.map(lambda x: jnp.array(x, copy=True), tree)
