"""Device seconds per search outside the ``bfs.expand`` scope: busy
time (a mean over the chips) less the expansion's, which leaves the
exchange between the chips and the merge (``bfs.exchange``), the
loop's termination test and what else the search runs."""
UNIT = "s"


def read(run):
    t, n = run.trace, len(run.record.searches)
    if t is None or not n or t.scope_s.get("bfs.expand", 0.0) <= 0:
        return None
    return (t.busy_s - t.scope_s["bfs.expand"]) / n
