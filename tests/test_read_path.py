"""Event-driven read path: window, readahead, hedging, routing bugfixes.

Covers the ISSUE-3 acceptance properties:
  * windowed/packetized reads return byte-identical data to the serial seed
    path (holes included) and beat it on the timeline,
  * sequential readahead pipelines forward scans, is invalidated on
    seek/write/truncate, and drains at the fsync/close barrier,
  * a straggler replica is dodged by the p99-budget hedge (result identical,
    charged latency far below the straggler's), and the budget adapts as the
    event timeline accumulates,
  * read-serving replicas land in ``read_affinity``, never the write-leader
    cache (leader-cache poisoning regression),
  * ``hedged_read_file`` reassembles sparse files correctly,
  * read-your-writes holds through the VFS (O_APPEND + pread) under a
    nonzero pipeline window,
  * same-seed reruns of the read suites are bit-identical.
"""

from __future__ import annotations

import pytest

from repro.core import (CfsCluster, LatencyModel, O_APPEND, O_CREAT, O_RDONLY,
                        O_RDWR, O_TRUNC, O_WRONLY, PACKET_SIZE)
from repro.core.client import _LatencyEwma
from repro.core.simnet import OpTimer
from repro.storage.datapipe import hedged_read_file

from benchmarks.common import run_streams


def _cluster(seed: int = 42, n_dp: int = 4):
    c = CfsCluster(n_meta=3, n_data=3, extent_max_size=8 * 1024 * 1024,
                   seed=seed)
    c.create_volume("v", n_meta_partitions=3, n_data_partitions=n_dp)
    return c


def _write(vfs, path: str, data: bytes) -> None:
    fd = vfs.open(path, O_WRONLY | O_CREAT | O_TRUNC)
    vfs.pwrite(fd, data, 0)
    vfs.close(fd)


# ---------------------------------------------------------------- fork race
def test_fork_join_first_resumes_at_winner():
    op = OpTimer(start_us=100.0, timed=True)
    fork = op.fork()
    op.add(50.0)
    fork.branch_done()              # branch A ends at 150
    op.add(20.0)
    fork.branch_done()              # branch B ends at 120
    op.add(999.0)
    fork.branch_done(record=False)  # failed branch: never wins
    fork.join_first()
    assert op.now_us == 120.0


def test_fork_join_first_without_ends_stays_at_fork_point():
    op = OpTimer(start_us=5.0, timed=True)
    fork = op.fork()
    op.add(33.0)
    fork.branch_done(record=False)
    fork.join_first()
    assert op.now_us == 5.0


# ----------------------------------------------------- windowed read = data
def test_windowed_read_matches_serial_including_holes():
    """Windowed/packetized fetches must assemble the same bytes as the
    serial seed path — including zero-filled holes from ftruncate-grow."""
    c = _cluster()
    vfs = c.mount("v", client_id="c0").vfs
    payload = bytes(range(256)) * (3 * PACKET_SIZE // 256)
    fd = vfs.open("/sparse.bin", O_RDWR | O_CREAT)
    vfs.pwrite(fd, payload, 0)
    vfs.ftruncate(fd, 5 * PACKET_SIZE)              # grow: hole in the middle
    vfs.pwrite(fd, b"tail" * 1024, 5 * PACKET_SIZE)  # beyond the hole
    vfs.close(fd)
    want = payload + bytes(5 * PACKET_SIZE - len(payload)) + b"tail" * 1024

    def read_all(window: int) -> bytes:
        v = c.mount("v", client_id=f"r{window}").vfs
        v.client.read_window = window
        op = c.net.begin_op(at=0.0)
        try:
            fd2 = v.open("/sparse.bin", O_RDONLY)
            data = v.read(fd2, -1)
            v.close(fd2)
        finally:
            c.net.end_op()
        return data

    assert read_all(0) == want
    assert read_all(8) == want


def test_windowed_read_beats_serial_on_the_timeline():
    c = _cluster()
    vfs = c.mount("v", client_id="c0").vfs
    _write(vfs, "/big.bin", bytes(16 * PACKET_SIZE))

    def whole_file_time(window: int) -> float:
        v = c.mount("v", client_id=f"t{window}").vfs
        v.client.read_window = window
        v.client.hedge_reads = False
        c.net.reset_accounting()       # fresh resource timelines per run
        op = c.net.begin_op(at=0.0)
        try:
            fd = v.open("/big.bin", O_RDONLY)
            assert len(v.read(fd, -1)) == 16 * PACKET_SIZE
            v.close(fd)
        finally:
            c.net.end_op()
        return op.us

    serial, windowed = whole_file_time(0), whole_file_time(8)
    assert windowed < 0.7 * serial, \
        f"window gained only {serial / windowed:.2f}x ({serial} vs {windowed})"


# -------------------------------------------------------------- readahead
def test_read_extents_at_with_zero_window_degrades_to_serial():
    """The detached prefetch primitive must not crash on a client pinned to
    the serial A/B setting (CFS_READ_WINDOW=0): it degrades to one fetch in
    flight."""
    c = _cluster()
    vfs = c.mount("v", client_id="c0").vfs
    payload = bytes(range(256)) * (2 * PACKET_SIZE // 256)
    _write(vfs, "/zw.bin", payload)
    cl = vfs.client
    cl.read_window = 0
    inode = cl.get_inode(vfs.path_inode("/zw.bin"))
    op = c.net.begin_op(at=0.0)
    try:
        data, done = cl.read_extents_at(inode, 0, len(payload), 0.0)
    finally:
        c.net.end_op()
    assert data == payload and done > 0.0


def test_readahead_pipelines_sequential_scan():
    c = _cluster()
    vfs = c.mount("v", client_id="c0").vfs
    data = bytes(range(256)) * (8 * PACKET_SIZE // 256)
    _write(vfs, "/scan.bin", data)

    def scan(window: int):
        v = c.mount("v", client_id=f"s{window}").vfs
        v.client.read_window = window
        v.client.hedge_reads = False
        hits0 = v.client.stats["ra_hits"]
        c.net.reset_accounting()       # fresh resource timelines per run
        op = c.net.begin_op(at=0.0)
        try:
            fd = v.open("/scan.bin", O_RDONLY)
            got = b"".join(v.read(fd, PACKET_SIZE) for _ in range(8))
            v.close(fd)
        finally:
            c.net.end_op()
        return got, op.us, v.client.stats["ra_hits"] - hits0

    got_s, t_serial, hits_s = scan(0)
    got_w, t_ra, hits_w = scan(8)
    assert got_s == data and got_w == data
    assert hits_s == 0
    assert hits_w >= 5, f"readahead served only {hits_w} of 8 reads"
    assert t_ra < t_serial


def test_readahead_invalidated_by_write_and_seek():
    """A forward scan must never serve stale prefetched bytes after an
    intervening write, and a seek resets the scan detection."""
    c = _cluster()
    vfs = c.mount("v", client_id="c0").vfs
    _write(vfs, "/inv.bin", b"a" * (6 * PACKET_SIZE))
    v = c.mount("v", client_id="c1").vfs
    op = c.net.begin_op(at=0.0)
    try:
        fd = v.open("/inv.bin", O_RDWR)
        v.read(fd, PACKET_SIZE)
        v.read(fd, PACKET_SIZE)            # scan confirmed: prefetch issued
        f = v.handle(fd)
        assert f._ra_chunks, "prefetch should be outstanding"
        # overwrite bytes the prefetch covers, through the same handle
        v.pwrite(fd, b"B" * PACKET_SIZE, 2 * PACKET_SIZE)
        assert not f._ra_chunks, "write must invalidate the readahead"
        got = v.pread(fd, PACKET_SIZE, 2 * PACKET_SIZE)
        assert got == b"B" * PACKET_SIZE
        v.close(fd)
    finally:
        c.net.end_op()


def test_readahead_invalidated_by_write_through_other_handle():
    """Regression: the readahead cache lives on the handle, but writes land
    at the client/data-node level — an overwrite through ANOTHER fd of the
    same client must invalidate every handle's cache (per-inode write
    version), or a scan serves stale pre-write bytes."""
    c = _cluster()
    vfs = c.mount("v", client_id="c0").vfs
    _write(vfs, "/x.bin", b"A" * (6 * PACKET_SIZE))
    v = c.mount("v", client_id="c1").vfs
    op = c.net.begin_op(at=0.0)
    try:
        fd1 = v.open("/x.bin", O_RDONLY)
        v.read(fd1, PACKET_SIZE)
        v.read(fd1, PACKET_SIZE)           # prefetch covers offset 2*PACKET
        assert v.handle(fd1)._ra_chunks
        fd2 = v.open("/x.bin", O_RDWR)
        v.pwrite(fd2, b"B" * PACKET_SIZE, 2 * PACKET_SIZE)
        v.close(fd2)
        got = v.read(fd1, PACKET_SIZE)     # same client, other handle
        assert got == b"B" * PACKET_SIZE, "stale readahead served"
        v.close(fd1)
    finally:
        c.net.end_op()


def test_readahead_drained_at_close_barrier():
    c = _cluster()
    vfs = c.mount("v", client_id="c0").vfs
    _write(vfs, "/drain.bin", bytes(8 * PACKET_SIZE))
    v = c.mount("v", client_id="c1").vfs
    op = c.net.begin_op(at=0.0)
    try:
        fd = v.open("/drain.bin", O_RDONLY)
        v.read(fd, PACKET_SIZE)
        v.read(fd, PACKET_SIZE)
        f = v.handle(fd)
        assert f._ra_chunks
        ready = max(r for (_s, _d, r) in f._ra_chunks)
        v.close(fd)
        assert op.now_us >= ready, "close must wait out in-flight readahead"
    finally:
        c.net.end_op()


# ------------------------------------------------------------------ hedging
def test_hedged_read_dodges_straggler_on_the_timeline():
    c = _cluster(n_dp=1)
    vfs = c.mount("v", client_id="c0").vfs
    _write(vfs, "/h.bin", b"q" * (2 * PACKET_SIZE))
    st = vfs.stat("/h.bin")
    pid = st["extents"][0][0]
    gid = f"dp{pid}"
    v = c.mount("v", client_id="c1").vfs
    cl = v.client
    cl.read_window = 8
    cl.data_cache = None    # a cached re-read would (correctly) never hedge

    def timed_pread(off):
        op = c.net.begin_op(at=0.0)
        try:
            fd = v.open("/h.bin", O_RDONLY)
            data = v.pread(fd, 4096, off)
            v.close(fd)
        finally:
            c.net.end_op()
        return data, op.us

    # warm the budget on straggler-free latencies
    for i in range(10):
        timed_pread(4096 * i)
    assert cl._hedge_budget(gid) is not None, "budget should be warm"
    n_before = cl._read_lat[gid].n
    leader = cl._dp(pid).replicas[0]
    cl.read_affinity.pop(gid, None)      # next read starts at the leader
    c.net.set_straggler(leader, 50_000.0)
    hedges0 = cl.stats["hedged_reads"]
    data, cost = timed_pread(0)
    c.net.set_straggler(leader, 0.0)
    assert data == b"q" * 4096                       # result identical
    assert cl.stats["hedged_reads"] > hedges0        # hedge fired
    assert cost < 50_000.0, f"hedge failed to dodge the straggler: {cost}"
    # the winner becomes the read affinity; the budget kept adapting
    assert cl.read_affinity[gid] != leader
    assert cl._read_lat[gid].n > n_before


def test_hedge_budget_adapts_with_the_timeline():
    e = _LatencyEwma()
    for _ in range(8):
        e.observe(100.0)
    low = e.p99_us
    assert low == pytest.approx(101.0)    # tight timeline -> tight budget
    for _ in range(8):
        e.observe(1000.0)
    assert e.p99_us > 5 * low             # tail widened -> budget follows
    for _ in range(64):
        e.observe(100.0)
    assert e.p99_us < 2.2 * low           # and relaxes back


def test_no_hedge_before_budget_warms():
    c = _cluster(n_dp=1)
    vfs = c.mount("v", client_id="c0").vfs
    _write(vfs, "/cold.bin", b"c" * PACKET_SIZE)
    v = c.mount("v", client_id="c1").vfs
    assert v.client._hedge_budget("dp999") is None
    op = c.net.begin_op(at=0.0)
    try:
        fd = v.open("/cold.bin", O_RDONLY)
        v.pread(fd, 4096, 0)
        v.close(fd)
    finally:
        c.net.end_op()
    assert v.client.stats["hedged_reads"] == 0


# ------------------------------------------- leader-cache poisoning (bugfix)
def test_follower_read_does_not_poison_write_leader_cache():
    """Regression: a read served by a follower used to be cached as the
    group's write leader, misrouting the next small-file write into a
    NotLeader retry round-trip."""
    c = _cluster(n_dp=1)
    vfs = c.mount("v", client_id="c0").vfs
    _write(vfs, "/seed.bin", bytes(2 * PACKET_SIZE))   # streams to the 1 dp
    cl = vfs.client
    st = vfs.stat("/seed.bin")
    pid = st["extents"][0][0]
    gid = f"dp{pid}"
    leader = cl._dp(pid).replicas[0]
    assert cl.leader_cache[gid] == leader
    # leader briefly unreachable: the read is served by a follower
    c.net.kill(leader)
    fd = vfs.open("/seed.bin", O_RDONLY)
    assert vfs.read(fd, PACKET_SIZE) == bytes(PACKET_SIZE)
    vfs.close(fd)
    c.net.revive(leader)
    assert cl.read_affinity[gid] != leader           # read affinity moved
    assert cl.leader_cache[gid] == leader            # write cache untouched
    # the next small-file write goes to the true leader FIRST: no NotLeader
    # retry is burned
    retries0 = cl.stats["retries"]
    _write(vfs, "/small.txt", b"x" * 1024)
    assert cl.stats["retries"] == retries0
    fd = vfs.open("/small.txt", O_RDONLY)
    assert vfs.read(fd, -1) == b"x" * 1024
    vfs.close(fd)


def test_nonleader_append_is_nakked():
    """A data node that is not the PB leader must refuse appends with a
    redirect hint instead of silently forking the chain."""
    from repro.core.raft import NotLeader
    c = _cluster(n_dp=1)
    vfs = c.mount("v", client_id="c0").vfs
    _write(vfs, "/nak.bin", bytes(PACKET_SIZE))
    cl = vfs.client
    pid = vfs.stat("/nak.bin")["extents"][0][0]
    dp = cl._dp(pid)
    follower = c.data_nodes[dp.replicas[1]]
    with pytest.raises(NotLeader) as ei:
        follower.serve_append(pid, 4242, 0, b"z", True)
    assert ei.value.leader_hint == dp.replicas[0]


def test_terminal_notleader_surfaces_as_fserror():
    """If every replica NAKs a write (e.g. mid-election, hint outside the
    client's partition view), _data_call must raise on the callers' error
    channel (FsError) — the append/small-write recovery paths catch
    (NetError, FsError), not raw raft NotLeader."""
    from repro.core.client import FsError, _DataPartition
    c = _cluster(n_dp=1)
    vfs = c.mount("v", client_id="c0").vfs
    _write(vfs, "/t.bin", bytes(PACKET_SIZE))
    cl = vfs.client
    pid = vfs.stat("/t.bin")["extents"][0][0]
    real = cl._dp(pid)
    # a partition view that only lists followers: every append NAKs with a
    # hint pointing outside this view
    fake = _DataPartition(pid=pid, replicas=list(real.replicas[1:]),
                          status="rw")
    with pytest.raises(FsError):
        cl._data_call(fake, "serve_append", 777, 0, b"z", True, nbytes=128)


# ------------------------------------------------------------ read assembly
_AS_SIZE = 5 * PACKET_SIZE + 4096
_AS_DATA = bytes(range(251)) * (3 * PACKET_SIZE // 251 + 1)
_AS_FILE = (_AS_DATA[:3 * PACKET_SIZE] + bytes(2 * PACKET_SIZE)
            + b"tail" * 1024)
_AS_CASES = {   # case: (offset, size, timed op with a read window)
    "inside_an_extent": (1000, 5000, False),
    "across_extents": (PACKET_SIZE - 1000, 2 * PACKET_SIZE, False),
    "hole_reads_zeros": (3 * PACKET_SIZE - 10, 2 * PACKET_SIZE + 20, False),
    "trailing_hole": (3 * PACKET_SIZE - 10, 2 * PACKET_SIZE + 20, False),
    "short_at_eof": (_AS_SIZE - 300, 4096, False),
    "timed_window": (0, PACKET_SIZE, True),
}


def _as_mount(extents=None):
    """A fresh cluster holding /as.bin: three packets of data, a hole from
    ftruncate-grow, then a tail (or, for ``trailing_hole``, no tail); and a
    new client to read it."""
    c = _cluster()
    vfs = c.mount("v", client_id="w").vfs
    fd = vfs.open("/as.bin", O_RDWR | O_CREAT)
    vfs.pwrite(fd, _AS_DATA, 0)
    vfs.ftruncate(fd, 3 * PACKET_SIZE)
    vfs.ftruncate(fd, 5 * PACKET_SIZE)
    if extents != "trailing_hole":
        vfs.pwrite(fd, b"tail" * 1024, 5 * PACKET_SIZE)
    vfs.close(fd)
    mnt = c.mount("v", client_id="r")
    mnt.client.read_window = 8
    mnt.client.hedge_reads = False
    return c, mnt


@pytest.mark.parametrize("case", list(_AS_CASES))
def test_read_assembles_the_file_bytes(case):
    """A read lays its pieces down by file offset: the bytes the file
    holds, holes (inside it and at its end) as zeros, a short read at EOF,
    the same bytes whatever order the extent map lists its extents in, and
    under a timed op with a read window (readahead hits included) the
    bytes of the untimed read.  What it returns is ``bytes``."""
    offset, size, timed = _AS_CASES[case]
    c, mnt = _as_mount(case)
    whole = (_AS_FILE if case != "trailing_hole"
             else _AS_FILE[:5 * PACKET_SIZE])
    want = whole[offset:offset + size]
    f = mnt.open("/as.bin", "r")
    f.seek(offset)
    if timed:
        c.net.begin_op(at=0.0)
        try:
            got = b"".join(iter(lambda: f.read(size), b""))
        finally:
            c.net.end_op()
        want = whole
        assert mnt.client.stats["ra_hits"] > 0
    else:
        got = f.read(size)
    assert type(got) is bytes and got == want
    if case == "short_at_eof":
        assert len(got) == 300
    if case in ("hole_reads_zeros", "trailing_hole"):
        assert got[10:10 + 2 * PACKET_SIZE] == bytes(2 * PACKET_SIZE)
    inode = mnt.stat("/as.bin")
    if case == "across_extents":
        assert any(offset < foff < offset + size
                   for _, _, foff, _, _ in inode["extents"])
    # the extent map in reverse order reads the same bytes
    backwards = dict(inode, extents=inode["extents"][::-1])
    assert len(inode["extents"]) > 1
    assert mnt.client.read_extents(backwards, offset, size) == \
        whole[offset:offset + size]


# ---------------------------------------------------- sparse hedged_read_file
def test_hedged_read_file_handles_sparse_files():
    """Regression: the old reassembly concatenated extents in map order,
    ignoring file offsets and holes — any ftruncate-grown file came back
    shifted/short."""
    c = _cluster()
    mnt = c.mount("v", client_id="c0")
    vfs = mnt.vfs
    head = b"H" * 4096
    tail = b"T" * 4096
    fd = vfs.open("/sp.bin", O_RDWR | O_CREAT)
    vfs.pwrite(fd, head, 0)
    vfs.ftruncate(fd, 3 * PACKET_SIZE)                 # hole after the head
    vfs.pwrite(fd, tail, 3 * PACKET_SIZE)
    vfs.close(fd)
    want = head + bytes(3 * PACKET_SIZE - 4096) + tail
    assert hedged_read_file(mnt, "/sp.bin") == want


# --------------------------------------------- VFS read-your-writes (O_APPEND)
def test_vfs_o_append_pread_drains_pipeline_window():
    """Read-your-writes through the VFS under CFS_PIPELINE_DEPTH>0: pread
    and read on an O_APPEND fd must observe every byte written through the
    still-open pipeline window (the read barrier drains it)."""
    c = _cluster()
    v = c.mount("v", client_id="c0").vfs
    v.client.pipeline_depth = 8
    op = c.net.begin_op(at=0.0)
    try:
        fd = v.open("/app.bin", O_RDWR | O_CREAT | O_APPEND)
        for i in range(4):
            v.write(fd, bytes([65 + i]) * PACKET_SIZE)
        assert v.handle(fd)._inflight, "window should be in flight"
        got = v.pread(fd, PACKET_SIZE, 3 * PACKET_SIZE)
        assert got == b"D" * PACKET_SIZE
        # interleave more appends and a sequential read from offset 0
        v.write(fd, b"E" * PACKET_SIZE)
        v.lseek(fd, 0)
        whole = v.read(fd, -1)
        assert whole == b"".join(
            bytes([65 + i]) * PACKET_SIZE for i in range(5))
        v.close(fd)
    finally:
        c.net.end_op()


# ------------------------------------------------------------- determinism
def _read_suite_trace(seed: int):
    """A miniature SeqRead+RandRead suite with window, readahead, hedging
    AND a straggler all active — the full read stack."""
    c = _cluster(seed=seed, n_dp=4)
    writer = c.mount("v", client_id="w").vfs
    for pi in range(3):
        _write(writer, f"/f{pi}.bin", bytes(8 * PACKET_SIZE))
    mounts = [c.mount("v", client_id=f"c{i}").vfs for i in range(2)]
    for m in mounts:
        m.client.read_window = 8
        m.client.hedge_reads = True
        # warm the budgets deterministically
        fd = m.open("/f0.bin", O_RDONLY)
        for _ in range(8):
            m.pread(fd, 4096, 0)
        m.close(fd)
    pid = mounts[0].stat("/f1.bin")["extents"][0][0]
    c.net.set_straggler(mounts[0].client._dp(pid).replicas[0], 20_000.0)

    streams = []
    for ci, m in enumerate(mounts):
        for pi in range(3):
            def ops(m=m, pi=pi):
                fd = m.open(f"/f{pi}.bin", O_RDONLY)
                for i in range(8):
                    yield lambda m=m, fd=fd: m.read(fd, PACKET_SIZE)
                for off in (4096, 999, 65536, 0):
                    yield lambda m=m, fd=fd, off=off: m.pread(fd, 4096, off)
            streams.append((f"c{ci}", ops()))
    trace = []
    r = run_streams("readmix", "cfs", c.net, streams, 2, 3, trace=trace)
    return trace, r


def test_read_suite_same_seed_runs_bit_identical():
    t1, r1 = _read_suite_trace(11)
    t2, r2 = _read_suite_trace(11)
    assert t1 == t2
    assert r1.sim_iops == r2.sim_iops
    assert (r1.p50_us, r1.p95_us, r1.p99_us) == (r2.p50_us, r2.p95_us,
                                                 r2.p99_us)
    assert r1.latency_us_per_op == r2.latency_us_per_op
    assert r1.bottleneck == r2.bottleneck
