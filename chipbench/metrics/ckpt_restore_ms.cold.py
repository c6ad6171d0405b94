"""``CheckpointManager.restore`` per cold start, as the program's
``ckpt.restore`` span sees it: the inside twin of ``restore_ms.cold``,
without the mount and the final ``block_until_ready``."""

from chipbench.program_spans import ms_per_cold_start


def read(run):
    return ms_per_cold_start(run, "ckpt.restore")
