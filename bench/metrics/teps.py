"""Graph500 TEPS as a rate: the Graph500 edges of every search of the
window over the wall time from the first search's dispatch to the
last one's ``block_until_ready``."""
UNIT = "edges/s"


def read(run):
    s = run.record.searches
    if not s:
        return None
    return sum(x["edges"] for x in s) / (s[-1]["ready"] - s[0]["dispatch"])
