"""The CRC32 of each checkpoint shard's bytes (``ckpt.crc32`` spans),
summed per cold start."""

from chipbench.program_spans import ms_per_cold_start


def read(run):
    return ms_per_cold_start(run, "ckpt.crc32")
