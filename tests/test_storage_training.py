"""Integration: CFS as the training substrate — checkpoint/restart,
deterministic replay, crash safety, hedged reads, elastic restore."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_arch
from repro.core import CfsCluster
from repro.storage.checkpoint import CheckpointManager, bytes_to_tensor
from repro.storage.datapipe import ShardReader, ShardWriter, hedged_read_file
from repro.train import optimizer as opt
from repro.train.trainer import Trainer, TrainerConfig


@pytest.fixture(scope="module")
def cluster():
    c = CfsCluster(n_meta=4, n_data=6, extent_max_size=1024 * 1024,
                   data_disk_capacity=4 * 1024 * 1024 * 1024)
    c.create_volume("train", n_meta_partitions=3, n_data_partitions=8)
    return c


@pytest.fixture(scope="module")
def data_volume(cluster):
    mnt = cluster.mount("train")
    w = ShardWriter(mnt, "/data", tokens_per_shard=4096)
    rng = np.random.RandomState(0)
    for d in range(8):
        # learnable structure: arithmetic token sequences with noise
        start = rng.randint(0, 97)
        doc = [(start + 3 * i) % 97 for i in range(3000)]
        w.add_document(doc)
    w.finish()
    return mnt


def make_trainer(cluster, mnt, base="/ckpt", seed=0):
    cfg = get_arch("minicpm-2b").reduced()
    oc = opt.opt_config_for(cfg, lr=1e-3, warmup_steps=2, total_steps=50)
    tc = TrainerConfig(ckpt_every=3, ckpt_base=base, max_steps=10)
    reader = ShardReader(mnt, "/data", rank=0, world=1, batch=2, seq_len=32)
    return Trainer(cfg, oc, tc, mnt, reader, seed=seed)


def test_loss_decreases(cluster, data_volume):
    t = make_trainer(cluster, data_volume, base="/ck_a")
    hist = t.train(10)
    first, last = hist[0]["loss"], hist[-1]["loss"]
    assert last < first, f"loss did not decrease: {first} -> {last}"


def test_crash_resume_is_bit_exact(cluster, data_volume):
    # uninterrupted run
    t1 = make_trainer(cluster, data_volume, base="/ck_b1", seed=1)
    t1.train(8)
    p_ref = t1.params

    # crash at step 5 (after the step-3 checkpoint), resume, finish
    t2 = make_trainer(cluster, data_volume, base="/ck_b2", seed=1)
    with pytest.raises(RuntimeError):
        t2.train(8, crash_at=5)
    t3 = make_trainer(cluster, data_volume, base="/ck_b2", seed=1)
    assert t3.resume()
    assert t3.step == 3          # last durable checkpoint
    t3.train(8 - t3.step)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(t3.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_checkpoint_crash_safety(cluster, data_volume):
    t = make_trainer(cluster, data_volume, base="/ck_c", seed=2)
    t.train(3)                   # durable ckpt at step 3
    t.train(2)
    with pytest.raises(RuntimeError):
        t.save(crash_after=3)    # dies mid-save of step-5 ckpt
    t2 = make_trainer(cluster, data_volume, base="/ck_c", seed=2)
    assert t2.resume()
    assert t2.step == 3          # torn step-5 ckpt invisible (no MANIFEST)


def test_checkpoint_detects_corruption(cluster, data_volume):
    mnt = cluster.mount("train")
    cm = CheckpointManager(mnt, "/ck_d", shards=2)
    tree = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
    cm.save(1, tree)
    # corrupt one shard on EVERY replica through the normal write path
    name = [n for n in mnt.readdir("/ck_d/step_1") if n != "MANIFEST"][0]
    f = mnt.open(f"/ck_d/step_1/{name}", "r+")
    f.seek(20)
    f.write(b"\xff\xff\xff")
    f.close()
    with pytest.raises(IOError):
        cm.restore({"w": np.zeros((8, 8), np.float32)})


def _leaves(dtype, seed=0):
    """Leaves of every shard layout: a matrix and a vector whose rows
    divide by 2 (two shards with ``shards=2``), a matrix whose rows do not
    and a scalar (one shard)."""
    rng = np.random.RandomState(seed)
    return {"a": rng.randn(6, 5).astype(dtype),
            "b": rng.randn(7, 3).astype(dtype),
            "c": rng.randn(10).astype(dtype),
            "d": np.asarray(rng.randn(), dtype)}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("shards", [1, 2])
def test_restore_matches_decoding_each_shard(cluster, shards, dtype):
    """Each leaf, its shards decoded straight into its host array, is bit
    for bit what ``bytes_to_tensor`` of each shard, concatenated, gives."""
    dt = np.dtype(jnp.bfloat16) if dtype == "bfloat16" else np.dtype(dtype)
    mnt = cluster.mount("train")
    base = f"/ck_rd_{shards}_{dtype}"
    tree = _leaves(dt)
    CheckpointManager(mnt, base, shards=shards).save(2, tree)
    manifest = json.loads(mnt.read_file(f"{base}/step_2/MANIFEST").decode())
    got, step = CheckpointManager(cluster.mount("train"), base).restore(
        {k: np.zeros(v.shape, dt) for k, v in tree.items()})
    assert step == 2
    for k, v in tree.items():
        entry = manifest["tensors"][k]
        parts = [bytes_to_tensor(mnt.read_file(sh["path"]))
                 for sh in entry["shards"]]
        want = np.concatenate(parts, 0) if len(parts) > 1 else parts[0]
        want = want.reshape(entry["shape"])
        assert len(entry["shards"]) == (2 if shards == 2 and k in "ac"
                                        else 1)
        assert got[k].dtype == want.dtype == dt
        assert got[k].shape == want.shape == v.shape
        assert got[k].tobytes() == want.tobytes() == v.tobytes()


def test_restore_casts_to_the_dtype_asked_for(cluster):
    mnt = cluster.mount("train")
    tree = _leaves(np.float32, seed=1)
    CheckpointManager(mnt, "/ck_cast", shards=2).save(1, tree)
    got, _ = CheckpointManager(mnt, "/ck_cast", shards=2).restore(
        jax.eval_shape(lambda: jax.tree.map(
            lambda x: jnp.asarray(x, jnp.bfloat16), tree)))
    for k, v in tree.items():
        assert got[k].dtype == jnp.bfloat16
        assert got[k].tobytes() == v.astype(jnp.bfloat16).tobytes()


@pytest.mark.parametrize("where", ["magic", "header_length", "header_json",
                                   "raw", "trailing_bytes"])
def test_restore_refuses_a_flipped_bit_before_put(cluster, where):
    """A bit flipped in a shard's header or in its rows, or bytes after its
    rows, raise IOError, and the leaf is never handed to ``put``; the
    leaves before it were."""
    mnt = cluster.mount("train")
    base = f"/ck_flip_{where}"
    tree = _leaves(np.float32, seed=2)
    CheckpointManager(mnt, base, shards=2).save(1, tree)
    path = f"{base}/step_1/b.shard0"
    size = mnt.stat(path)["size"]
    f = mnt.open(path, "r+")
    if where == "trailing_bytes":
        f.seek(size)
        f.write(b"\0" * 4)
    else:
        at = {"magic": 1, "header_length": 4, "header_json": 12,
              "raw": size - 3}[where]
        f.seek(at)
        byte = f.read(1)[0]
        f.seek(at)
        f.write(bytes([byte ^ 0x10]))
    f.close()
    put = []
    with pytest.raises(IOError):
        CheckpointManager(cluster.mount("train"), base).restore(
            {k: np.zeros(v.shape, v.dtype) for k, v in tree.items()},
            put=lambda arr: put.append(arr) or arr)
    assert len(put) == 1 and put[0].tobytes() == tree["a"].tobytes()


def test_restored_leaves_are_writable_and_their_own(cluster):
    mnt = cluster.mount("train")
    tree = _leaves(np.float32, seed=3)
    CheckpointManager(mnt, "/ck_own", shards=2).save(1, tree)
    got, _ = CheckpointManager(mnt, "/ck_own", shards=2).restore(
        {k: np.zeros(v.shape, v.dtype) for k, v in tree.items()})
    for k, x in got.items():
        assert x.flags.writeable
        assert not any(np.shares_memory(x, y) for j, y in got.items()
                       if j != k)
    got["a"][...] = 0
    for k in "bcd":
        assert got[k].tobytes() == tree[k].tobytes()


def test_elastic_restore_different_shard_count(cluster, data_volume):
    mnt = cluster.mount("train")
    tree = {"emb": np.random.RandomState(3).randn(16, 8).astype(np.float32)}
    cm4 = CheckpointManager(mnt, "/ck_e", shards=4)
    cm4.save(7, tree)
    cm2 = CheckpointManager(mnt, "/ck_e", shards=2)   # different topology
    restored, step = cm2.restore({"emb": np.zeros((16, 8), np.float32)})
    assert step == 7
    np.testing.assert_array_equal(restored["emb"], tree["emb"])


def test_hedged_read_avoids_straggler(cluster, data_volume):
    mnt = cluster.mount("train")
    mnt.write_file("/hedge.bin", b"z" * 4096)
    st = mnt.stat("/hedge.bin")
    pid = st["extents"][0][0]
    dp = mnt.client._dp(pid)
    leader = dp.replicas[0]
    # make the leader a 50 ms straggler
    cluster.net.set_straggler(leader, 50_000.0)
    mnt.client.leader_cache[f"dp{pid}"] = leader
    op = cluster.net.begin_op()
    data = hedged_read_file(mnt, "/hedge.bin", hedge_us=5_000.0)
    cost = cluster.net.end_op().us
    cluster.net.set_straggler(leader, 0.0)
    assert data == b"z" * 4096
    assert cost < 50_000.0, f"hedge failed to dodge the straggler: {cost}us"
    # the fast replica wins the READ affinity; the write-leader cache must
    # keep pointing at the true leader (poisoning it misroutes writes)
    assert mnt.client.read_affinity[f"dp{pid}"] != leader
    assert mnt.client.leader_cache[f"dp{pid}"] == leader


def test_datapipe_deterministic_batches(cluster, data_volume):
    r1 = ShardReader(data_volume, "/data", 0, 2, batch=2, seq_len=16)
    r2 = ShardReader(data_volume, "/data", 0, 2, batch=2, seq_len=16)
    b1, b2 = r1.batch_at(5), r2.batch_at(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # ranks see disjoint shards
    ra = ShardReader(data_volume, "/data", 0, 2, batch=2, seq_len=16)
    rb = ShardReader(data_volume, "/data", 1, 2, batch=2, seq_len=16)
    assert not set(ra.my_shards()) & set(rb.my_shards())


def test_serving_batch_slots(cluster):
    from repro.serve.server import BatchServer, Request
    cfg = get_arch("codeqwen1.5-7b").reduced()
    from repro.models import get_model
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0), jnp.float32)
    srv = BatchServer(cfg, params, batch=2, smax=64)
    reqs = [Request(rid=i, prompt=[1 + i, 2 + i, 3 + i], max_new=4)
            for i in range(5)]
    with obs.recording() as rec:
        done = srv.serve(reqs)
    assert len(done) == 5
    for r in done:
        assert len(r.out) == 4
        assert all(0 <= t < cfg.vocab for t in r.out)
    # one span a wave, counting its requests and the tokens they got
    waves = [s.counts for s in rec.spans if s.name == "server.wave"]
    assert waves == [{"slots": 2, "tokens": 8}, {"slots": 2, "tokens": 8},
                     {"slots": 1, "tokens": 4}]
