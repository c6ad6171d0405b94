"""The ``put`` of each restored leaf, the copy to the chip as the host
sees it (``ckpt.put`` spans), summed per cold start."""

from chipbench.program_spans import ms_per_cold_start


def read(run):
    return ms_per_cold_start(run, "ckpt.put")
