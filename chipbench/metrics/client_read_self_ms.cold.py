"""``CfsFile.read`` less its extent fetches: the assembly of the fetched
pieces and the copies of the whole file (self time of the ``client.read``
spans), summed per cold start."""

from chipbench.program_spans import ms_per_cold_start


def read(run):
    return ms_per_cold_start(run, "client.read", "self_seconds")
