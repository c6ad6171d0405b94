"""``BatchServer.serve`` of the first wave after a cold start: the
benchmark's span around the call, which ends in the server's host read of
the tokens."""


def read(run):
    spans = [s for s in run["spans"] if s.name == "serve"]
    if not spans:
        return None
    return 1e3 * sum(s.seconds for s in spans) / len(spans)
