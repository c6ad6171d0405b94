"""Integration: CFS as the training substrate — checkpoint/restart,
deterministic replay, crash safety, hedged reads, elastic restore."""

import dataclasses
import itertools
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs import get_arch
from repro.core import CfsCluster
from repro.storage.checkpoint import (CheckpointManager, _parse_header,
                                      tensor_to_bytes)
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


def bytes_to_tensor(data: bytes) -> np.ndarray:
    """The oracle: a tensor file decoded into a host array of its own."""
    dtype, shape, offset = _parse_header(data)
    return np.frombuffer(data, dtype=dtype,
                         offset=offset).reshape(shape).copy()


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
    leaves before it were, shard by shard."""
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
    # a's two shards went up, b's did not
    assert len(put) == 2
    assert np.concatenate(put).tobytes() == tree["a"].tobytes()


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


def _on_device(tree):
    """``like`` as a serving replica or ``Trainer.resume`` gives it."""
    return jax.eval_shape(lambda: jax.tree.map(jnp.asarray, tree))


def _host_copy_bytes(rec):
    return obs.totals(rec.spans)["ckpt.restore"]["host_copy_bytes"]


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_host_described_leaves_go_to_put_by_shard(cluster, shards):
    """Leaves described by host arrays take the device leaves' rule: with
    ``put=jnp.asarray`` each is a ``jax.Array``, bit for bit what
    ``bytes_to_tensor`` of each shard, concatenated, gives, and no row went
    through a host copy."""
    mnt = cluster.mount("train")
    base = f"/ck_host_{shards}"
    tree = dict(_leaves(np.float32, seed=10),
                e=np.random.RandomState(11).randn(8, 3).astype(np.float32))
    CheckpointManager(mnt, base, shards=shards).save(1, tree)
    manifest = json.loads(mnt.read_file(f"{base}/step_1/MANIFEST").decode())
    with obs.recording() as rec:
        got, _ = CheckpointManager(cluster.mount("train"), base).restore(
            {k: np.zeros(v.shape, v.dtype) for k, v in tree.items()},
            put=jnp.asarray)
    assert _host_copy_bytes(rec) == 0
    for k, v in tree.items():
        parts = [bytes_to_tensor(mnt.read_file(sh["path"]))
                 for sh in manifest["tensors"][k]["shards"]]
        want = np.concatenate(parts, 0) if len(parts) > 1 else parts[0]
        assert isinstance(got[k], jax.Array)
        assert got[k].dtype == want.dtype and got[k].shape == v.shape
        assert np.asarray(got[k]).tobytes() == want.tobytes() == v.tobytes()


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_restore_to_the_device_matches_decoding_each_shard(cluster, shards,
                                                           dtype):
    """With device-described leaves and ``put=jnp.asarray`` each leaf is a
    ``jax.Array``, bit for bit what ``bytes_to_tensor`` of each shard,
    concatenated, gives, and no row went through a host copy."""
    dt = np.dtype(jnp.bfloat16) if dtype == "bfloat16" else np.dtype(dtype)
    mnt = cluster.mount("train")
    base = f"/ck_dev_{shards}_{dtype}"
    tree = dict(_leaves(dt, seed=4),
                e=np.random.RandomState(5).randn(8, 3).astype(dt))
    CheckpointManager(mnt, base, shards=shards).save(3, tree)
    manifest = json.loads(mnt.read_file(f"{base}/step_3/MANIFEST").decode())
    with obs.recording() as rec:
        got, step = CheckpointManager(cluster.mount("train"), base).restore(
            _on_device(tree), put=jnp.asarray)
    assert step == 3
    assert _host_copy_bytes(rec) == 0
    for k, v in tree.items():
        entry = manifest["tensors"][k]
        parts = [bytes_to_tensor(mnt.read_file(sh["path"]))
                 for sh in entry["shards"]]
        want = np.concatenate(parts, 0) if len(parts) > 1 else parts[0]
        rows = v.shape[0] if v.ndim else 0
        assert len(parts) == (shards if rows >= shards and rows % shards == 0
                              else 1)
        assert isinstance(got[k], jax.Array)
        assert got[k].dtype == want.dtype == dt
        assert got[k].shape == want.shape == v.shape
        assert np.asarray(got[k]).tobytes() == want.tobytes() == v.tobytes()


def test_restore_to_the_device_refuses_a_flipped_bit_before_put(cluster):
    """A bit flipped in shard 1's rows raises IOError, and shard 0 of that
    leaf never reaches ``put``; the leaves before it did."""
    mnt = cluster.mount("train")
    base = "/ck_dev_flip"
    tree = _leaves(np.float32, seed=6)
    CheckpointManager(mnt, base, shards=2).save(1, tree)
    path = f"{base}/step_1/c.shard1"
    at = mnt.stat(path)["size"] - 3
    f = mnt.open(path, "r+")
    f.seek(at)
    byte = f.read(1)[0]
    f.seek(at)
    f.write(bytes([byte ^ 0x10]))
    f.close()
    put = []
    with pytest.raises(IOError):
        CheckpointManager(cluster.mount("train"), base).restore(
            _on_device(tree), put=lambda arr: put.append(arr) or
            jnp.asarray(arr))
    # a (two shards) and b (one) went up; c did not
    assert [p.tobytes() for p in put] == [
        tree["a"][:3].tobytes(), tree["a"][3:].tobytes(), tree["b"].tobytes()]


def test_host_puts_of_device_leaves_are_writable_and_their_own(cluster):
    """A ``put`` that stays on the host, under device-described leaves,
    still gives leaves that are writable and share no memory with the
    bytes read or with one another."""
    mnt = cluster.mount("train")
    tree = _leaves(np.float32, seed=9)
    CheckpointManager(mnt, "/ck_dev_own", shards=2).save(1, tree)
    with obs.recording() as rec:
        got, _ = CheckpointManager(mnt, "/ck_dev_own").restore(
            _on_device(tree))
    assert _host_copy_bytes(rec) == sum(v.nbytes for v in tree.values())
    for k, x in got.items():
        assert isinstance(x, np.ndarray) and x.flags.writeable
        assert x.tobytes() == tree[k].tobytes()
    got["a"][...] = 0
    got["b"][...] = 0
    for k in "cd":
        assert got[k].tobytes() == tree[k].tobytes()


@pytest.mark.parametrize("shape,dtype", [((6, 5), "float32"),
                                         ((10,), "bfloat16"),
                                         ((), "float32"),
                                         ((3, 4, 7), "int8")])
def test_shard_rows_start_at_a_multiple_of_64_bytes(cluster, shape, dtype):
    """The header is padded with spaces: a saved shard's rows start at a
    multiple of 64 bytes, and the file still decodes."""
    dt = np.dtype(jnp.bfloat16) if dtype == "bfloat16" else np.dtype(dtype)
    arr = np.asarray(np.random.RandomState(7).randn(*shape) * 50).astype(dt)
    data = tensor_to_bytes(arr)
    hlen = int.from_bytes(data[4:8], "little")
    assert (8 + hlen) % 64 == 0
    assert len(data) == 8 + hlen + arr.nbytes
    assert bytes_to_tensor(data).tobytes() == arr.tobytes()
    mnt = cluster.mount("train")
    base = f"/ck_align_{dtype}_{len(shape)}"
    CheckpointManager(mnt, base, shards=2).save(1, {"x": arr})
    for name in mnt.readdir(f"{base}/step_1"):
        if name != "MANIFEST":
            shard = mnt.read_file(f"{base}/step_1/{name}")
            assert (8 + int.from_bytes(shard[4:8], "little")) % 64 == 0


def _unpadded(arr: np.ndarray) -> bytes:
    """A shard file as written before headers were padded."""
    header = json.dumps({"dtype": str(arr.dtype),
                         "shape": list(arr.shape)}).encode()
    return (b"RPT1" + len(header).to_bytes(4, "little") + header
            + arr.tobytes())


@pytest.mark.parametrize("on_device", [True, False])
def test_a_shard_in_the_unpadded_layout_still_restores(cluster, on_device):
    """Shards written before the header was padded (here at an odd offset,
    so their float32 rows are not aligned) restore bit for bit, and the
    device path counts their rows as copied on the host."""
    import zlib
    mnt = cluster.mount("train")
    base = f"/ck_old_{on_device}"
    tree = {"a": np.random.RandomState(8).randn(6, 5).astype(np.float32)}
    mnt.mkdir(base)
    mnt.mkdir(f"{base}/step_1")
    shards = []
    for k, part in enumerate(np.split(tree["a"], 2)):
        data = _unpadded(part)
        assert int.from_bytes(data[4:8], "little") % 2 == 1
        path = f"{base}/step_1/a.shard{k}"
        mnt.write_file(path, data)
        shards.append({"path": path, "bytes": len(data),
                       "crc32": zlib.crc32(data) & 0xFFFFFFFF})
    manifest = {"step": 1, "tensors": {"a": {
        "shards": shards, "dtype": "float32", "shape": [6, 5]}}}
    mnt.write_file(f"{base}/step_1/MANIFEST", json.dumps(manifest).encode())
    mnt.write_file(f"{base}/LATEST", b"1")
    like = _on_device(tree) if on_device else {"a": np.zeros((6, 5),
                                                             np.float32)}
    with obs.recording() as rec:
        got, step = CheckpointManager(cluster.mount("train"), base).restore(
            like, put=jnp.asarray)
    assert step == 1
    assert isinstance(got["a"], jax.Array)
    assert np.asarray(got["a"]).tobytes() == tree["a"].tobytes()
    assert _host_copy_bytes(rec) == tree["a"].nbytes


class _LoggedMount:
    """A mount that logs each shard file it has read, in order, into
    ``log``, beside the ``put`` calls a test logs there."""

    def __init__(self, mnt, log):
        self._mnt, self.log = mnt, log

    def read_file(self, path):
        data = self._mnt.read_file(path)
        if ".shard" in path:
            self.log.append(("read", path))
        return data

    def __getattr__(self, name):
        return getattr(self._mnt, name)


def _logged_put(log, on_device):
    def put(arr):
        log.append(("put", arr.nbytes))
        return jnp.asarray(arr) if on_device else arr
    return put


@pytest.mark.parametrize("on_device", [True, False])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("shards", [1, 2])
def test_restore_reads_one_shard_ahead_of_the_last_put(cluster, shards,
                                                       dtype, on_device):
    """Each shard's CRC runs behind the next shard's read: a leaf's shards
    reach ``put`` once every shard of it and the first of the next leaf
    have been read (all of them after the last leaf), never later and
    never sooner.  The leaves are bit for bit what ``bytes_to_tensor`` of
    each shard, concatenated, gives, on the host and on the device, and
    no thread outlives the restore."""
    dt = np.dtype(jnp.bfloat16) if dtype == "bfloat16" else np.dtype(dtype)
    mnt = cluster.mount("train")
    base = f"/ck_ahead_{shards}_{dtype}_{on_device}"
    tree = dict(_leaves(dt, seed=12),
                e=np.random.RandomState(13).randn(8, 3).astype(dt))
    CheckpointManager(mnt, base, shards=shards).save(1, tree)
    manifest = json.loads(mnt.read_file(f"{base}/step_1/MANIFEST").decode())
    like = (_on_device(tree) if on_device
            else {k: np.zeros(v.shape, v.dtype) for k, v in tree.items()})
    log = []
    threads = threading.active_count()
    got, _ = CheckpointManager(
        _LoggedMount(cluster.mount("train"), log), base).restore(
            like, put=_logged_put(log, on_device))
    assert threading.active_count() == threads
    # the flat shard order, and where each leaf's shards end in it
    paths = [sh["path"] for k in sorted(tree)
             for sh in manifest["tensors"][k]["shards"]]
    ends = list(itertools.accumulate(
        len(manifest["tensors"][k]["shards"]) for k in sorted(tree)))
    assert [p for what, p in log if what == "read"] == paths
    puts = [n for n, (what, _) in enumerate(log) if what == "put"]
    assert len(puts) == len(paths)
    for n, at in enumerate(puts):
        end = next(e for e in ends if e > n)
        read_before = sum(what == "read" for what, _ in log[:at])
        assert read_before == min(end + 1, len(paths)), (paths[n], log)
    for k, v in tree.items():
        parts = [bytes_to_tensor(mnt.read_file(sh["path"]))
                 for sh in manifest["tensors"][k]["shards"]]
        want = np.concatenate(parts, 0) if len(parts) > 1 else parts[0]
        assert isinstance(got[k], jax.Array if on_device else np.ndarray)
        assert got[k].dtype == want.dtype == dt
        assert got[k].shape == want.shape == v.shape
        assert np.asarray(got[k]).tobytes() == want.tobytes() == v.tobytes()


@pytest.mark.parametrize("on_device", [True, False])
@pytest.mark.parametrize("shard", ["c.shard1", "e.shard1"])
def test_a_flipped_bit_behind_the_next_read_stops_its_leaf_before_put(
        cluster, shard, on_device):
    """A bit flipped in the rows of a middle leaf's shard, or of the last
    leaf's last shard (whose CRC has no later read to run behind), raises
    IOError naming the shard; no shard of that leaf reaches ``put``, every
    shard of the leaves before it did, and no thread outlives the
    restore."""
    mnt = cluster.mount("train")
    base = f"/ck_flip_ahead_{shard}_{on_device}"
    tree = dict(_leaves(np.float32, seed=14),
                e=np.random.RandomState(15).randn(8, 3).astype(np.float32))
    CheckpointManager(mnt, base, shards=2).save(1, tree)
    path = f"{base}/step_1/{shard}"
    at = mnt.stat(path)["size"] - 3
    f = mnt.open(path, "r+")
    f.seek(at)
    byte = f.read(1)[0]
    f.seek(at)
    f.write(bytes([byte ^ 0x10]))
    f.close()
    like = (_on_device(tree) if on_device
            else {k: np.zeros(v.shape, v.dtype) for k, v in tree.items()})
    put = []
    threads = threading.active_count()
    with pytest.raises(IOError, match=f"checksum mismatch in {path}$"):
        CheckpointManager(cluster.mount("train"), base).restore(
            like, put=lambda arr: put.append(arr) or (
                jnp.asarray(arr) if on_device else arr))
    assert threading.active_count() == threads
    before = "abcd"[: "abcde".index(shard[0])]
    want = [r for k in before
            for r in (np.split(tree[k], 2) if k in "ace" else [tree[k]])]
    assert [p.tobytes() for p in put] == [r.tobytes() for r in want]


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
