"""Closed loop of whole searches through `repro.bfs.plan`.

Graph500 kernel 2 as Graph500 times it: one search after another,
each ``plan(g).run_batched(roots)`` ended by ``block_until_ready``.
The configuration's optional ``spec`` holds `TraversalSpec` fields;
without it the default spec runs.  Set-up compiles the one
whole-search program (``lower().compile()``; no warm-up search: the
first search of a window runs no slower than the rest).  The window
opens at the first dispatch and ends at the first search boundary
past ``--seconds``; parents are read back after it closes.

Traffic parameters: ``batch`` (roots per search), ``roots`` (how many
distinct roots to draw among vertices of degree > 0).  The roots keep
the structure seed's order (`roots`), so every seed's window holds the
same searches under its own labels: the window ends on a search
boundary, and a seeded order would change which roots fall inside
it.
"""
from __future__ import annotations

import time

import numpy as np

from bench import traffic as gen
from bench import work


def roots(ctx) -> np.ndarray:
    """The search roots, in the order the window runs them."""
    return gen.roots(ctx.seed, ctx.degrees, int(ctx.cell.traffic["roots"]),
                     replace=False, fixed=ctx.fixed, shuffle=False)


def run(ctx):
    import repro.bfs as bfs
    from bench.harness import Record

    batch = int(ctx.cell.traffic["batch"])
    keys = roots(ctx)
    spec = ctx.cell.config.get("spec")
    ct = bfs.plan(ctx.graph, spec and bfs.TraversalSpec(**spec))
    ct.lower(keys[:batch]).compile()
    n_vertices = ctx.graph.n_vertices

    win = ctx.window
    done = []
    t0 = win.open()
    i = 0
    while time.perf_counter() - t0 < ctx.seconds:
        r = keys[(i * batch) % len(keys):][:batch]
        with win.annotate("bench.search"):
            t_dispatch = time.perf_counter()
            res = ct.run_batched(r)
            res.state.parent.block_until_ready()
            t_ready = time.perf_counter()
        done.append((r, t_dispatch, t_ready, res))
        i += 1
    win.close()

    record = Record(window_s=done[-1][2] - done[0][1],
                    attempted=len(done) * batch)
    for r, t_dispatch, t_ready, res in done:
        parents = np.asarray(bfs.parents_graph500(res.state, n_vertices))
        layers = np.asarray(res.depths).tolist()
        record.searches.append({
            "roots": r.tolist(), "dispatch": t_dispatch, "ready": t_ready,
            "layers": layers,
            "edges": sum(work.search_edges(ctx.degrees, p) for p in parents)})
        record.trees.extend(zip(r.tolist(), parents))
    record.notes.update(
        searches=len(done), spec=str(ct.resolved.to_dict()),
        search_s=[round(s["ready"] - s["dispatch"], 4)
                  for s in record.searches],
        layers=[s["layers"] for s in record.searches])
    return record
