"""Queries served per second: each query's share of its ticks that
ended inside the window, summed, over the time from the window's
open to the end of its last tick.

A query holds its slot for ``layers`` ticks, the last of which
(``tick``, counted from 1) harvested it; only queries that came back
whole count.  A query in flight at the close counts for the share it
had done, so the rate does not step by whole queries."""
UNIT = "queries/s"


def read(run):
    rec = run.record
    k = sum(t <= rec.window_s for t in rec.tick_ends)
    if not k:
        return None
    served = 0.0
    for q in rec.queries:
        if q["whole"] and q["tick"] is not None and q["layers"]:
            first = q["tick"] - q["layers"]     # the tick before its first
            served += max(0, min(q["tick"], k) - first) / q["layers"]
    return served / rec.tick_ends[k - 1]
