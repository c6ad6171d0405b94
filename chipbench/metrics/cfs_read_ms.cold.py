"""Time in the CFS client's ``read_file`` per cold start: the benchmark's
spans around each ``read_file`` of the mount it hands to
``CheckpointManager``, summed over the window and divided by its cold
starts."""


def read(run):
    spans = [s for s in run["spans"] if s.name == "cfs_read"]
    n = run["result"].get("cold_starts")
    if not spans or not n:
        return None
    return 1e3 * sum(s.seconds for s in spans) / n
