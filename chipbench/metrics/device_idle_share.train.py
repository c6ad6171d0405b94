"""Share of the traced training window in which no op ran on the chip:
1 - (union of the device's op intervals / window), from the profiler
trace."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["chips"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
