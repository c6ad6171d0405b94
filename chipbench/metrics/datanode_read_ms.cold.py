"""``DataNode.serve_read``, the extent store's read (``datanode.read``
spans), summed per cold start."""

from chipbench.program_spans import ms_per_cold_start


def read(run):
    return ms_per_cold_start(run, "datanode.read")
