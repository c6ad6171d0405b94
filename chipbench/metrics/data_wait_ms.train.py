"""Host time the trainer waits for ``ShardReader.batch_at``, per step.

Reads the benchmark's ``batch_at`` spans (a wrapper around every
``batch_at`` the ``Trainer`` makes in the window), summed and divided by
the window's steps."""


def read(run):
    spans = [s for s in run["spans"] if s.name == "batch_at"]
    steps = run["result"].get("steps")
    if not spans or not steps:
        return None
    return 1e3 * sum(s.seconds for s in spans) / steps
