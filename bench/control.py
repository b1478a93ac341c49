"""The control of the comparison that decides `correct`.

The configurations state no precision; the guarantee they state is
an exact BFS tree.  The control is the plain reference with its level
barrier taken out, the shortcut that would tempt a later change: one
sweep per layer over the vertices in blocks, in which a vertex found
earlier in the sweep already expands later in the same sweep (an
in-place, Gauss-Seidel update of the frontier).  It reaches every
vertex the reference reaches, but some parents lie more than one
level up, so its trees have to fail the comparison.

    python bench/control.py --workload <cell> --seeds 1 2 3

puts the control in the place of the cell's driver and drives the
rest of a run through the harness (`bench.harness.run_cell`): the
cell's graph at its own size, the first ``--roots`` of the roots the
cell's driver draws, and the harness's own comparison, which has to
decide ``correct: false``.  It prints each run's result line (the
benchmark's own runs never run it).
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time

import numpy as np

if __name__ == "__main__":
    _ROOT = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness, reference  # noqa: E402

BLOCKS = 64


def control_parents(g: reference.HostGraph, root: int,
                    blocks: int = BLOCKS) -> np.ndarray:
    """Parents (-1 unreached) of the barrier-free sweep from ``root``."""
    v = g.n_vertices
    parent = np.full(v, -1, np.int64)
    parent[root] = root
    frontier = np.zeros(v, bool)
    frontier[root] = True
    bounds = np.linspace(0, v, blocks + 1).astype(np.int64)
    while frontier.any():
        found = np.zeros(v, bool)
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            owners = lo + np.flatnonzero(frontier[lo:hi] | found[lo:hi])
            if not owners.size:
                continue
            frontier[owners] = found[owners] = False
            starts = g.offsets[owners]
            counts = g.offsets[owners + 1] - starts
            slot = np.repeat(starts - np.cumsum(counts) + counts, counts) \
                + np.arange(int(counts.sum()))
            src = np.repeat(owners, counts)
            dst = g.adj[slot]
            fresh = parent[dst] < 0
            dst, first = np.unique(dst[fresh], return_index=True)
            parent[dst] = src[fresh][first]
            found[dst] = True   # a later block of this sweep expands it
        frontier = found
    return parent


def drive(ctx, n_roots: int) -> harness.Record:
    """The control in the driver's place: its trees from the first
    ``n_roots`` roots that the cell's own driver draws."""
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{ctx.cell.traffic['driver']}.py")
    roots = driver.roots(ctx)[:n_roots]
    g = reference.host_graph(np.asarray(ctx.edges[0]),
                             np.asarray(ctx.edges[1]), len(ctx.degrees))
    win = ctx.window
    t0 = win.open()
    trees = [(int(r), control_parents(g, int(r))) for r in roots]
    t1 = win.close()
    return harness.Record(window_s=t1 - t0, attempted=len(trees),
                          trees=trees)


def run(cell, seed: int, n_roots: int, seconds: float,
        require_tpu: bool = True):
    """The result object of one harness run of ``cell`` with the
    control in its driver's place (None where the device check
    fails); ``seconds`` is the window the cell's roots are drawn for."""
    out = harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                           require_tpu,
                           drive=functools.partial(drive, n_roots=n_roots))
    return out and out[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--roots", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=json.loads(
        (harness.ROOT / "BENCHMARK.json").read_text())["run_seconds"])
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    from repro import compile_cache
    compile_cache.enable()
    for seed in args.seeds:
        result = run(cell, seed, args.roots, args.seconds)
        if result is None:
            return harness.NO_DEVICE
        print(json.dumps(dict(result, seed=seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
