"""Work that does not depend on what implements the search.

`search_edges` is the Graph500 edge count of one search (half the
directed degree sum of the reached vertices, the rule of the
Graph500 specification and of `core/stats.py`).  `bytes_per_search`
is the top-down minimum of one BFS over the graph: every adjacency
slot read once (4 bytes) and one offset and one parent word per
vertex (8 bytes).  `peak` reads the chip's published peaks, keyed by
``device_kind``; a kind missing from the table is an error.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def search_edges(degrees: np.ndarray, parent) -> int:
    """Graph500 edges of a search whose tree is ``parent`` (-1 where
    unreached): half the directed degree sum of the reached
    vertices."""
    reached = np.asarray(parent).reshape(-1) >= 0
    return int(np.asarray(degrees, np.int64)[reached].sum()) // 2


def bytes_per_search(n_vertices: int, n_directed_slots: int) -> int:
    """``4 * E_directed + 8 * V``: the least bytes one top-down BFS
    moves (a bottom-up path may read fewer slots)."""
    return 4 * int(n_directed_slots) + 8 * int(n_vertices)


def peak(device_kind: str, key: str = "hbm_bytes_per_s") -> float:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {PEAKS.name}; known: "
                       f"{sorted(table)}")
    return float(table[device_kind][key])
