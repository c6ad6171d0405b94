"""Print the client-mechanism tables: batched metadata RPCs, metadata
sessions, async metadata and QoS, each measured on the simulated cluster.

    PYTHONPATH=src python -m benchmarks.report
"""

from __future__ import annotations


def meta_batch_report(n_files: int = 64) -> None:
    """§VFS — metadata RPC coalescing on the mdtest create+fill workload:
    batched (λFS-style) vs the seed scatter path, same cluster shape."""
    from repro.core import CfsCluster, O_CREAT, O_TRUNC, O_WRONLY

    def run(coalesce: bool):
        c = CfsCluster(n_meta=4, n_data=6, extent_max_size=1024 * 1024,
                       seed=9)
        c.create_volume("bench", 3, 8)
        vfs = c.mount("bench").vfs
        vfs.client.coalesce_meta = coalesce
        vfs.mkdir("/md")
        for i in range(n_files):
            fd = vfs.open(f"/md/f{i}", O_WRONLY | O_CREAT | O_TRUNC)
            vfs.pwrite(fd, b"x" * 1024, 0)
            vfs.close(fd)
        return vfs.client.stats

    batched, scatter = run(True), run(False)
    print("## §VFS — batched metadata RPCs "
          f"(mdtest create+fill, {n_files} files)\n")
    print("| path | meta_calls | batched ops | round-trips saved |")
    print("|---|---|---|---|")
    print(f"| scatter (seed) | {scatter['meta_calls']} | - | - |")
    print(f"| meta_batch | {batched['meta_calls']} |"
          f" {batched['meta_batched_ops']} |"
          f" {batched['meta_saved_roundtrips']} |")
    pct = (1 - batched["meta_calls"] / scatter["meta_calls"]) * 100
    print(f"\nmetadata round-trips: -{pct:.0f}% vs seed\n")


def meta_session_report(n_rounds: int = 64) -> None:
    """§Sessions — the lease/version cache on a stat/open/ENOENT loop:
    session (default TTLs) vs the seed sync-on-open path (TTL=0), same
    cluster shape, one timed op stream so leases are live."""
    from repro.core import CfsCluster, O_CREAT, O_TRUNC, O_WRONLY

    def run(ttl):
        c = CfsCluster(n_meta=4, n_data=6, extent_max_size=1024 * 1024,
                       seed=9)
        c.create_volume("bench", 3, 8)
        vfs = c.mount("bench").vfs
        if ttl is not None:
            vfs.client.session.ttl_us = ttl
        vfs.mkdir("/md")
        for i in range(8):
            fd = vfs.open(f"/md/f{i}", O_WRONLY | O_CREAT | O_TRUNC)
            vfs.close(fd)
        c.net.reset_accounting()
        base = dict(vfs.client.stats)
        op = c.net.begin_op(at=0.0)         # timed: the lease clock is live
        try:
            for i in range(n_rounds):
                vfs.stat(f"/md/f{i % 8}")
                vfs.close(vfs.open(f"/md/f{(3 * i) % 8}"))
                vfs.exists("/md/nope")
        finally:
            c.net.end_op()
        return {k: vfs.client.stats[k] - base.get(k, 0)
                for k in ("meta_calls", "meta_cache_hits",
                          "meta_cache_misses", "neg_hits",
                          "lease_revalidations")}

    lease, sync = run(None), run(0.0)
    print(f"## §Sessions — leased metadata cache "
          f"(stat/open/ENOENT loop, {n_rounds} rounds)\n")
    print("| path | meta_calls | hits | neg_hits | misses | revalidations |")
    print("|---|---|---|---|---|---|")
    print(f"| sync-on-open (seed, TTL=0) | {sync['meta_calls']} | - | - |"
          f" - | - |")
    print(f"| session (leases) | {lease['meta_calls']} |"
          f" {lease['meta_cache_hits']} | {lease['neg_hits']} |"
          f" {lease['meta_cache_misses']} | {lease['lease_revalidations']} |")
    pct = (1 - lease["meta_calls"] / max(sync["meta_calls"], 1)) * 100
    print(f"\nmetadata RPCs on the stat/open path: -{pct:.0f}% vs seed\n")


def meta_async_report(n_dirs: int = 64, barrier_every: int = 16) -> None:
    """§Async commits — early-ack namespace mutations on a create burst
    with periodic dir-fsync durability barriers: async (journal + bounded
    window) vs the seed raft-round-per-mutation ack path, same cluster
    shape, one timed op stream."""
    from repro.core import CfsCluster, O_RDONLY

    def run(async_on: bool):
        c = CfsCluster(n_meta=4, n_data=6, extent_max_size=1024 * 1024,
                       seed=9)
        c.create_volume("bench", 3, 8)
        vfs = c.mount("bench").vfs
        vfs.client.meta_async = async_on
        vfs.mkdir("/md")
        c.net.reset_accounting()
        base = dict(vfs.client.stats)
        op = c.net.begin_op(at=0.0)
        try:
            for i in range(n_dirs):
                vfs.mkdir(f"/md/d{i}")
                if (i + 1) % barrier_every == 0:
                    fd = vfs.open("/md", O_RDONLY)
                    vfs.fsync(fd)              # dir-fsync durability barrier
                    vfs.close(fd)
        finally:
            c.net.end_op()
        drains = sorted(e["commit_us"] - e["ack_us"]
                        for node in c.meta_nodes.values()
                        for entries in node.journal.values()
                        for e in entries)
        stats = {k: vfs.client.stats[k] - base.get(k, 0)
                 for k in ("meta_async_acks", "meta_async_stalls",
                           "meta_barriers", "meta_barrier_stalls",
                           "meta_barrier_stall_us")}
        stats["makespan_us"] = op.us
        stats["drains"] = drains
        return stats

    def pctl(xs, q):
        if not xs:
            return 0.0
        import math
        return xs[min(max(1, math.ceil(q * len(xs))), len(xs)) - 1]

    a, s = run(True), run(False)
    print(f"## §Async commits — early-ack mkdir burst ({n_dirs} dirs, "
          f"dir-fsync every {barrier_every})\n")
    print("| path | makespan µs | acks | window stalls | barriers |"
          " barrier stalls | stall µs | drain p50 µs | drain p99 µs |")
    print("|---|---|---|---|---|---|---|---|---|")
    print(f"| sync (seed) | {s['makespan_us']:.1f} | - | - |"
          f" {s['meta_barriers']} | {s['meta_barrier_stalls']} |"
          f" {s['meta_barrier_stall_us']:.1f} | - | - |")
    print(f"| async (journal) | {a['makespan_us']:.1f} |"
          f" {a['meta_async_acks']} | {a['meta_async_stalls']} |"
          f" {a['meta_barriers']} | {a['meta_barrier_stalls']} |"
          f" {a['meta_barrier_stall_us']:.1f} |"
          f" {pctl(a['drains'], 0.5):.1f} | {pctl(a['drains'], 0.99):.1f} |")
    pct = (1 - a["makespan_us"] / max(s["makespan_us"], 1e-9)) * 100
    print(f"\ncreate-burst makespan: -{pct:.0f}% vs seed (barriers pay the "
          "raft round; un-barriered creates ride the window)\n")


def qos_report() -> None:
    """§QoS — per-volume NIC accounting under two-tenant contention: a
    victim stat/open stream vs a noisy DirCreation burst on shared meta
    nodes, with the WFQ/admission machinery on vs off.  Uses the
    per-volume breakdown from :meth:`CfsClient.qos_volume_stats` and
    names the offending tenant (dominant queued_us share)."""
    from .common import percentile, run_streams
    from .qos import _aggressor_streams, _make_cluster, _victim_streams

    print("## §QoS — per-volume weighted fair queueing "
          "(victim stat/open vs noisy DirCreation)\n")
    print("| qos | volume | meta rpcs | queued µs | sheds | retries |"
          " victim p99 µs |")
    print("|---|---|---|---|---|---|---|")
    offender, offender_q = "-", -1.0
    for qos_on in (True, False):
        c = _make_cluster()
        c.net.qos = qos_on
        victim = _victim_streams(c, 1, 4, 12)
        agg_mounts: list = []
        streams = victim + _aggressor_streams(c, 2, 8, 8, agg_mounts)
        lat_by: list = []
        run_streams("QosReport", "cfs", c.net, streams, 3, 8,
                    lat_by_stream=lat_by)
        vlat = sorted(x for ls in lat_by[:len(victim)] for x in ls)
        p99 = percentile(vlat, 0.99)
        per = agg_mounts[0].client.qos_volume_stats()
        for m in agg_mounts[1:]:       # fold every aggressor client's sheds
            per["noisy"]["sheds"] += m.client.stats["qos_sheds"]
            per["noisy"]["retries"] += m.client.stats["qos_shed_retries"]
        label = "on" if qos_on else "off"
        for vol in sorted(per):
            s = per[vol]
            if not qos_on and s["queued_us"] > offender_q:
                offender, offender_q = vol, s["queued_us"]
            p99c = f"{p99:.1f}" if vol == "victim" else "-"
            print(f"| {label} | {vol} | {s['rpcs']} | {s['queued_us']:.0f} |"
                  f" {s['sheds']} | {s['retries']} | {p99c} |")
    print(f"\noffending tenant (dominant queued µs with qos off): "
          f"**{offender}** — WFQ pins the victim's tail at its isolated "
          "baseline while the offender pays the queueing it causes\n")


def main() -> None:
    meta_batch_report()
    meta_session_report()
    meta_async_report()
    qos_report()


if __name__ == "__main__":
    main()
