"""``CheckpointManager.restore`` per cold start: the benchmark's span
around each restore call, ending in ``block_until_ready`` of the restored
weights."""


def read(run):
    spans = [s for s in run["spans"] if s.name == "restore"]
    if not spans:
        return None
    return 1e3 * sum(s.seconds for s in spans) / len(spans)
