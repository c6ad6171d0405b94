"""``CfsClient._read_one`` less the data node's own read: routing, the
simulated network's charge and failover (self time of the
``client.fetch`` spans), summed per cold start."""

from chipbench.program_spans import ms_per_cold_start


def read(run):
    return ms_per_cold_start(run, "client.fetch", "self_seconds")
