"""Device seconds under the ``bfs.expand`` scope per search in the
traced window, as a mean over the chips (each chip sweeps its own
slots under that scope)."""
UNIT = "s"


def read(run):
    t, n = run.trace, len(run.record.searches)
    if t is None or not n or t.scope_s.get("bfs.expand", 0.0) <= 0:
        return None
    return t.scope_s["bfs.expand"] / n
