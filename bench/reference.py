"""The plain reference: a level-synchronous BFS in NumPy on the
benchmark's own edge list, and the comparison that decides `correct`.

Nothing here imports the program.  A BFS tree from the program is
right when it reaches exactly the vertices the reference reaches,
the root is its own parent, and every other reached vertex's parent
is one of its neighbours lying exactly one reference level above it.
That holds for every valid BFS tree and for no other parent array,
so the count of vertices that break it is compared with the limit 0.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class HostGraph(NamedTuple):
    """Sorted adjacency of the symmetrized edge list, on the host."""
    n_vertices: int
    offsets: np.ndarray     # (V + 1,) int64
    adj: np.ndarray         # (E,) int64, each list sorted
    keys: np.ndarray        # (E,) int64, src * V + dst, sorted

    @property
    def degrees(self) -> np.ndarray:
        return np.diff(self.offsets)


def host_graph(src, dst, n_vertices: int) -> HostGraph:
    """Build the sorted adjacency from directed ``(src, dst)`` slots."""
    v = int(n_vertices)
    keys = np.asarray(src, np.int64) * v + np.asarray(dst, np.int64)
    keys.sort()
    owners = keys // v
    offsets = np.zeros(v + 1, np.int64)
    np.cumsum(np.bincount(owners, minlength=v), out=offsets[1:])
    return HostGraph(v, offsets, keys - owners * v, keys)


def bfs_levels(g: HostGraph, root: int) -> np.ndarray:
    """BFS level of every vertex from ``root`` (-1 where unreached)."""
    level = np.full(g.n_vertices, -1, np.int64)
    level[root] = 0
    frontier = np.array([root], np.int64)
    depth = 0
    while frontier.size:
        starts = g.offsets[frontier]
        counts = g.offsets[frontier + 1] - starts
        slot = np.repeat(starts - np.cumsum(counts) + counts, counts) \
            + np.arange(int(counts.sum()))
        nbrs = np.unique(g.adj[slot])
        frontier = nbrs[level[nbrs] < 0]
        depth += 1
        level[frontier] = depth
    return level


def wrong_vertices(g: HostGraph, parent, root: int,
                   level: np.ndarray | None = None) -> int:
    """Vertices whose entry in ``parent`` (Graph500 convention: -1
    unreached, the root its own parent) breaks the BFS-tree rule of
    the module docstring.  0 for every valid BFS tree from ``root``."""
    if level is None:
        level = bfs_levels(g, root)
    v = g.n_vertices
    p = np.asarray(parent, np.int64).reshape(-1)
    if p.shape[0] != v:
        return v
    reached = level >= 0
    bad = (p >= 0) != reached
    bad[root] |= p[root] != root
    child = np.flatnonzero(reached & (p >= 0))
    child = child[child != root]
    par = p[child]
    in_range = (par >= 0) & (par < v)
    par_ok = np.where(in_range, par, 0)
    up_one = in_range & (level[par_ok] == level[child] - 1)
    key = child * v + par_ok
    pos = np.minimum(np.searchsorted(g.keys, key), g.keys.shape[0] - 1)
    adjacent = g.keys[pos] == key
    bad[child] |= ~(up_one & adjacent)
    return int(bad.sum())
