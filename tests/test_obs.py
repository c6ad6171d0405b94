"""``repro.obs``: the program's span recorder, and the spans of a
checkpoint restore through a cluster."""

import itertools
import time

import numpy as np
import pytest

from repro import obs
from repro.core import CfsCluster
from repro.storage.checkpoint import CheckpointManager


@pytest.fixture
def clock(monkeypatch):
    """A clock that reads 0, 1, 2, ... seconds, one step a read."""
    ticks = itertools.count()
    monkeypatch.setattr(obs, "perf_counter", lambda: float(next(ticks)))


def test_off_records_nothing_and_shares_one_null_context():
    a = obs.span("a", bytes=3)
    b = obs.span("b")
    assert a is b
    with a as sp:
        sp.add(bytes=5)
    with obs.recording() as rec:
        pass
    with obs.span("after"):
        pass
    assert rec.spans == []


def test_nesting_and_parent_ids(clock):
    with obs.recording() as rec:
        with obs.span("outer"):
            with obs.span("mid"):
                with obs.span("leaf"):
                    pass
            with obs.span("leaf"):
                pass
        with obs.span("top"):
            pass
    by = {(s.name, s.start): s for s in rec.spans}
    outer, mid = by["outer", 0.0], by["mid", 1.0]
    leaf1, leaf2, top = by["leaf", 2.0], by["leaf", 5.0], by["top", 8.0]
    assert outer.parent is None and top.parent is None
    assert mid.parent == outer.id and leaf2.parent == outer.id
    assert leaf1.parent == mid.id
    assert len({s.id for s in rec.spans}) == 5
    # closed innermost first
    assert [s.name for s in rec.spans] == ["leaf", "mid", "leaf", "outer",
                                           "top"]


def test_self_time_and_summed_counts(clock):
    with obs.recording() as rec:
        with obs.span("read", bytes=10) as sp:     # 0 .. 7
            with obs.span("fetch", bytes=4):       # 1 .. 2
                pass
            with obs.span("fetch", bytes=6) as f:  # 3 .. 6
                with obs.span("disk"):             # 4 .. 5
                    pass
                f.add(attempts=2)
            sp.add(bytes=1, attempts=1)
    t = obs.totals(rec.spans)
    assert t["read"] == {"count": 1, "seconds": 7.0, "self_seconds": 3.0,
                         "bytes": 11, "attempts": 1}
    assert t["fetch"] == {"count": 2, "seconds": 4.0, "self_seconds": 3.0,
                          "bytes": 10, "attempts": 2}
    assert t["disk"] == {"count": 1, "seconds": 1.0, "self_seconds": 1.0}
    assert sum(v["self_seconds"] for v in t.values()) == t["read"]["seconds"]


def test_an_exception_closes_the_span_and_passes_through():
    with obs.recording() as rec:
        with pytest.raises(KeyError):
            with obs.span("outer"):
                with obs.span("inner"):
                    raise KeyError("x")
        with obs.span("next"):
            pass
    by = {s.name: s for s in rec.spans}
    assert by["inner"].parent == by["outer"].id
    assert by["next"].parent is None
    assert all(s.end >= s.start for s in rec.spans)


def test_recording_nests_and_restores_the_outer_recorder():
    with obs.recording() as outer:
        with obs.span("a"):
            pass
        with obs.recording() as inner:
            with obs.span("b"):
                pass
        with obs.span("c"):
            pass
    assert [s.name for s in outer.spans] == ["a", "c"]
    assert [s.name for s in inner.spans] == ["b"]
    assert obs.span("d") is obs.span("e")



def test_under_the_profiler_spans_are_kept_without_a_recording(tmp_path):
    """While a jax profiler trace collects, a span outside any recording
    is kept (and annotated); a recording still takes its own spans, and
    once the profiler stops a span is the null context again."""
    import jax
    with jax.profiler.trace(str(tmp_path / "a")):
        with obs.span("outer", bytes=2):
            with obs.span("inner"):
                pass
        with obs.recording() as rec:
            with obs.span("recorded"):
                pass
    assert obs.span("after") is obs.span("other")
    got = obs.profiled()
    assert [s.name for s in got] == ["inner", "outer"]
    assert got[0].parent == got[1].id and got[1].counts == {"bytes": 2}
    assert [s.name for s in rec.spans] == ["recorded"]
    # the next trace starts anew
    with jax.profiler.trace(str(tmp_path / "b")):
        with obs.span("second"):
            pass
    assert [s.name for s in obs.profiled()] == ["second"]


# ------------------------------------------------------------- checkpoint

RESTORE_PARTS = ("client.open", "client.read", "client.fetch",
                 "datanode.read", "ckpt.crc32", "ckpt.decode", "ckpt.put")


@pytest.fixture(scope="module")
def saved():
    cluster = CfsCluster(n_meta=4, n_data=6, extent_max_size=256 * 1024)
    cluster.create_volume("v", 3, 4)
    rng = np.random.default_rng(0)
    tree = {"w": rng.standard_normal((64, 3000)).astype(np.float32),
            "b": rng.standard_normal(7).astype(np.float32),
            "n": np.arange(12, dtype=np.int32)}
    CheckpointManager(cluster.mount("v"), "/ck", shards=2).save(3, tree)
    return cluster, tree


def _restore(cluster, tree):
    ckpt = CheckpointManager(cluster.mount("v"), "/ck", shards=2)
    return ckpt.restore(tree)


def test_restore_spans_nest_as_the_read_path_does(saved):
    cluster, tree = saved
    with obs.recording() as rec:
        _restore(cluster, tree)
    by_id = {s.id: s for s in rec.spans}
    parent = {s.id: by_id[s.parent].name if s.parent else None
              for s in rec.spans}
    allowed = {"ckpt.restore": {None},
               "client.open": {"ckpt.restore"},
               "client.read": {"ckpt.restore"},
               "client.fetch": {"client.read"},
               "datanode.read": {"client.fetch"},
               "ckpt.crc32": {"ckpt.restore"},
               "ckpt.decode": {"ckpt.restore"},
               "ckpt.put": {"ckpt.restore"},
               "ckpt.crc32_work": {None}}
    for s in rec.spans:
        assert parent[s.id] in allowed[s.name], (s.name, parent[s.id])
    t = obs.totals(rec.spans)
    assert set(t) == set(allowed)
    assert t["ckpt.restore"]["count"] == 1
    # each fetch asked one replica, which answered with its bytes
    assert t["client.fetch"]["attempts"] == t["client.fetch"]["count"]
    assert t["datanode.read"]["bytes"] == t["client.fetch"]["bytes"]


def test_crc_bytes_are_the_checkpoints_shard_bytes(saved):
    cluster, tree = saved
    import json
    mnt = cluster.mount("v")
    manifest = json.loads(mnt.read_file("/ck/step_3/MANIFEST").decode())
    shards = [sh for t in manifest["tensors"].values() for sh in t["shards"]]
    with obs.recording() as rec:
        _restore(cluster, tree)
    t = obs.totals(rec.spans)
    shard_bytes = sum(sh["bytes"] for sh in shards)
    assert t["ckpt.crc32"]["count"] == len(shards)
    assert t["ckpt.crc32"]["bytes"] == shard_bytes
    assert t["ckpt.restore"]["bytes"] == shard_bytes
    assert t["ckpt.put"]["bytes"] == sum(x.nbytes for x in tree.values())


def test_self_times_add_up_to_the_restore(saved):
    cluster, tree = saved
    with obs.recording() as rec:
        _restore(cluster, tree)
    t = obs.totals(rec.spans)
    parts = t["ckpt.restore"]["self_seconds"] + sum(
        t[n]["self_seconds"] for n in RESTORE_PARTS)
    assert parts == pytest.approx(t["ckpt.restore"]["seconds"], rel=1e-9)
    # the leaves have no children of their own
    for n in ("client.open", "datanode.read", "ckpt.crc32", "ckpt.decode",
              "ckpt.put"):
        assert t[n]["self_seconds"] == pytest.approx(t[n]["seconds"])


def test_restored_leaves_are_the_same_with_recording_on_and_off(saved):
    cluster, tree = saved
    off, step_off = _restore(cluster, tree)
    with obs.recording(annotate=True):
        on, step_on = _restore(cluster, tree)
    assert step_off == step_on == 3
    for k in tree:
        assert on[k].dtype == off[k].dtype == tree[k].dtype
        assert on[k].tobytes() == off[k].tobytes() == tree[k].tobytes()


def test_cluster_stats_are_the_same_with_recording_on_and_off(saved):
    """Spans read no simulated clock and touch no counter: a restore costs
    the cluster the same with the recorder on."""
    cluster, tree = saved

    def stats(on):
        mnt = cluster.mount("v")
        ckpt = CheckpointManager(mnt, "/ck", shards=2)
        if on:
            with obs.recording():
                ckpt.restore(tree)
        else:
            ckpt.restore(tree)
        return dict(mnt.client.stats)

    assert stats(False) == stats(True)


class _SlowMount:
    """A mount whose every read takes 50 ms more, as a shard's read over
    the network takes several times its CRC."""

    def __init__(self, mnt):
        self._mnt = mnt

    def read_file(self, path):
        data = self._mnt.read_file(path)
        time.sleep(0.05)
        return data

    def __getattr__(self, name):
        return getattr(self._mnt, name)


@pytest.fixture(scope="module")
def saved_large():
    """Four leaves of two 2 MiB shards each: ``zlib`` lets go of the
    interpreter lock over buffers this size."""
    cluster = CfsCluster(n_meta=4, n_data=6, extent_max_size=1024 * 1024)
    cluster.create_volume("v", 3, 8)
    rng = np.random.default_rng(1)
    tree = {f"w{i}": rng.standard_normal((8, 2 ** 17)).astype(np.float32)
            for i in range(4)}
    CheckpointManager(cluster.mount("v"), "/ck", shards=2).save(1, tree)
    return cluster, tree


def test_crc_spans_time_the_wait_behind_the_next_read(saved_large):
    """Each ``ckpt.crc32`` span is the restore's wait for one shard's CRC,
    opened under ``ckpt.restore``, counting the shard's ``bytes`` and
    ``ready``; every CRC but the last is done behind the next shard's read
    before it is asked for, and the self times still split the restore."""
    cluster, tree = saved_large
    with obs.recording() as rec:
        got, _ = CheckpointManager(_SlowMount(cluster.mount("v")),
                                   "/ck").restore(tree)
    by_id = {s.id: s for s in rec.spans}
    crcs = [s for s in rec.spans if s.name == "ckpt.crc32"]
    assert len(crcs) == 8
    for s in crcs:
        assert by_id[s.parent].name == "ckpt.restore"
        assert set(s.counts) == {"bytes", "ready"}
        assert s.counts["bytes"] > 2 * 2 ** 20
    assert sum(s.counts["ready"] for s in crcs) >= len(crcs) - 1
    t = obs.totals(rec.spans)
    parts = t["ckpt.restore"]["self_seconds"] + sum(
        t[n]["self_seconds"] for n in RESTORE_PARTS)
    assert parts == pytest.approx(t["ckpt.restore"]["seconds"], rel=1e-9)
    for k, v in tree.items():
        assert got[k].tobytes() == v.tobytes()


def test_crc_work_is_timed_on_the_worker(saved_large):
    """The CRC's own work stays readable: one ``ckpt.crc32_work`` span per
    shard, a root span of the worker thread (so it splits nothing of the
    restore's), with the shard's ``bytes``, inside the restore's time."""
    cluster, tree = saved_large
    with obs.recording() as rec:
        CheckpointManager(_SlowMount(cluster.mount("v")), "/ck").restore(tree)
    restore, = [s for s in rec.spans if s.name == "ckpt.restore"]
    work = [s for s in rec.spans if s.name == "ckpt.crc32_work"]
    waits = [s for s in rec.spans if s.name == "ckpt.crc32"]
    assert len(work) == len(waits) == 8
    assert sorted(s.counts["bytes"] for s in work) == \
        sorted(s.counts["bytes"] for s in waits)
    for s in work:
        assert s.parent is None and set(s.counts) == {"bytes"}
        assert restore.start <= s.start and s.end <= restore.end
        assert s.seconds > 0
