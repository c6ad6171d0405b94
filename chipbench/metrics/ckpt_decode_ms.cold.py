"""Shard bytes to arrays (``bytes_to_tensor``) and each leaf's
concatenate, reshape and cast (``ckpt.decode`` spans), summed per cold
start."""

from chipbench.program_spans import ms_per_cold_start


def read(run):
    return ms_per_cold_start(run, "ckpt.decode")
