"""A wave of ``BatchServer.serve`` (``server.wave`` spans), averaged over
the waves: the inside twin of ``first_wave_ms.cold``."""

from chipbench.program_spans import ms_per_span


def read(run):
    return ms_per_span(run, "server.wave")
