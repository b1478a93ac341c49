"""Per cent of the HBM roofline the expansion reaches over the mesh:
the least time one search needs with the chips sharing its bytes,
`bench.work.bytes_per_search` over (chips x the chip's peak
bandwidth), over the measured ``bfs.expand`` device seconds per
search (a mean over the chips)."""
from bench import work

UNIT = "%"


def read(run):
    t, n = run.trace, len(run.record.searches)
    if t is None or not n or t.scope_s.get("bfs.expand", 0.0) <= 0:
        return None
    least_s = work.bytes_per_search(run.n_vertices, run.n_slots) \
        / (run.cell.chips * work.peak(run.device_kind))
    return 100.0 * least_s / (t.scope_s["bfs.expand"] / n)
