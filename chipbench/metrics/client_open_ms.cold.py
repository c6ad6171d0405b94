"""Metadata resolution of each file the restore opens
(``CfsVfs.open_file``, ``client.open`` spans), summed per cold start."""

from chipbench.program_spans import ms_per_cold_start


def read(run):
    return ms_per_cold_start(run, "client.open")
