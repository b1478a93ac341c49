"""Compressed Sparse Row graph representation — paper §3.3.1, Fig. 4.

``rows`` holds the concatenated adjacency lists, ``colstarts[u]`` /
``colstarts[u+1]`` delimit vertex ``u``'s neighbors.  Adjacency lists
are sorted, which the validator exploits for binary-searched edge
membership tests.

Data alignment (paper §4.2): the Xeon Phi wants 64-byte boundaries and
suffers peel/remainder loops when it doesn't get them.  The TPU
analogue is 128-lane alignment.  We therefore

* pad ``rows`` to a multiple of ``LANES`` (=128) with a **sentinel
  vertex** ``V``;
* size every vertex-indexed array (bitmaps, P) for
  ``padded_vertex_count(V)`` vertices; and
* pre-mark all padding vertices as *visited* at BFS init.

Padding lanes then flow through the full gather-test-mask-scatter
pipeline and always filter out — the masks replace the paper's peel and
remainder special cases, with zero branches.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmap as bm
from repro.core.rmat import EdgeList
from repro.errors import GraphValidationError

LANES = 128  # TPU vector lane count; the "64-byte boundary" analogue.


def round_up(x: int, m: int) -> int:
    return (int(x) + m - 1) // m * m


def padded_vertex_count(n_vertices: int) -> int:
    """Vertex-array size: V real vertices + sentinel V + lane padding."""
    return round_up(n_vertices + 1, LANES)


class Csr(NamedTuple):
    rows: jax.Array        # (n_edges_padded,) int32, sentinel-padded
    colstarts: jax.Array   # (n_vertices + 1,) int32
    n_vertices: int        # real vertex count V (sentinel id == V)
    n_edges: int           # real directed edge count (un-padded)

    @property
    def n_vertices_padded(self) -> int:
        return padded_vertex_count(self.n_vertices)

    @property
    def n_edges_padded(self) -> int:
        return int(self.rows.shape[0])

    @property
    def sentinel(self) -> int:
        return self.n_vertices

    def degrees(self) -> jax.Array:
        return self.colstarts[1:] - self.colstarts[:-1]

    def out_degree(self, u) -> jax.Array:
        return self.colstarts[u + 1] - self.colstarts[u]


@jax.jit
def _sort_edges(src: jax.Array, dst: jax.Array):
    """Lexicographic (src, dst) sort via two stable passes.

    Avoids the int64 composite key (x64 is disabled; E < 2^31 and
    V < 2^31 are framework invariants, asserted in from_edges).
    """
    order1 = jnp.argsort(dst, stable=True)
    src1, dst1 = src[order1], dst[order1]
    order2 = jnp.argsort(src1, stable=True)
    return src1[order2], dst1[order2]


def from_edges(edges: EdgeList) -> Csr:
    """Build a padded CSR from a COO edge list (Graph500 kernel 2)."""
    v = edges.n_vertices
    assert v < 2**31 and edges.src.shape[0] < 2**31, \
        "int32 representation requires V, E < 2^31 (enable x64 beyond)"
    rows, colstarts = _csr_arrays(edges.src, edges.dst, v)
    n_edges = int(rows.shape[0])
    pad = round_up(n_edges, LANES) - n_edges
    if pad:
        rows = jnp.concatenate([rows, jnp.full((pad,), v, dtype=jnp.int32)])
    return Csr(rows=rows, colstarts=colstarts, n_vertices=v,
               n_edges=n_edges)


@functools.partial(jax.jit, static_argnums=2)
def _csr_arrays(src: jax.Array, dst: jax.Array, n_vertices: int):
    """Unpadded ``(rows, colstarts)`` of a COO edge list, in one
    program: the sort's and the count's temporaries share one memory
    plan.  At Graph500 scale 24 that plan takes 12.06 GiB of a v5e's
    15.75 GiB, edge list included; op by op the count's temporaries
    came on top of both sorted arrays and ran out of memory."""
    src, dst = _sort_edges(src, dst)
    counts = jnp.bincount(src, length=n_vertices).astype(jnp.int32)
    colstarts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)])
    return dst.astype(jnp.int32), colstarts


def padding_premarked_visited(n_vertices: int) -> jax.Array:
    """Visited bitmap with every padding vertex pre-marked.

    This replaces the paper's peel/remainder loop handling: sentinel
    lanes always test as 'already visited' and drop out of the masks.
    The single home of the convention — `init_visited`, the fused
    engine's batched init and `formats.GraphFormat.init_visited` all
    derive from it.
    """
    v_pad = padded_vertex_count(n_vertices)
    vis = bm.zeros(v_pad)
    pad_ids = jnp.arange(n_vertices, v_pad, dtype=jnp.int32)
    return bm.set_bits_exact(vis, pad_ids)


def init_visited(csr: Csr) -> jax.Array:
    """`padding_premarked_visited` for a built CSR."""
    return padding_premarked_visited(csr.n_vertices)


def _as_count(name: str, value) -> int:
    """Coerce a geometry scalar to a non-negative int or raise
    `GraphValidationError` (NaN/inf/fractional/negative all name the
    invariant)."""
    if isinstance(value, bool):
        raise GraphValidationError(
            f"{name} must be a non-negative integer, got the bool "
            f"{value!r}; pass a vertex/edge count")
    if isinstance(value, float):
        if math.isnan(value) or math.isinf(value) \
                or value != int(value):
            raise GraphValidationError(
                f"{name} must be a non-negative integer, got {value!r} "
                f"(NaN/inf/fractional geometry would silently mis-size "
                f"every vertex-indexed array); pass an exact int")
        value = int(value)
    if not isinstance(value, (int, np.integer)):
        raise GraphValidationError(
            f"{name} must be a non-negative integer, got "
            f"{type(value).__name__} {value!r}")
    value = int(value)
    if value < 0:
        raise GraphValidationError(
            f"{name} must be >= 0, got {value}")
    return value


def check_structure(csr: Csr) -> Csr:
    """Strict admission-time structural validation (ISSUE 8).

    Raises `repro.errors.GraphValidationError` (which IS-A
    ``ValueError``) when the CSR could produce a *wrong traversal*
    rather than an error: non-monotone ``colstarts``, out-of-range
    neighbor ids, float/NaN geometry, mismatched edge counts, wrong
    dtypes.  Every message names the violated invariant and the fix.

    Tracer-held arrays (a `Csr` flowing through a jitted legacy shim)
    skip the data checks — values are unreadable at trace time; the
    geometry scalars, which are always Python ints, are still checked.
    Returns ``csr`` so call sites can chain.
    """
    v = _as_count("n_vertices", csr.n_vertices)
    e = _as_count("n_edges", csr.n_edges)
    if v < 1:
        raise GraphValidationError(
            "n_vertices must be >= 1 (a BFS needs at least a root "
            "vertex); got 0")
    try:
        rows = np.asarray(csr.rows)
        colstarts = np.asarray(csr.colstarts)
    except Exception:
        return csr  # tracer-held: data checked at concrete admission
    for name, arr in (("rows", rows), ("colstarts", colstarts)):
        if arr.ndim != 1:
            raise GraphValidationError(
                f"{name} must be 1-D, got shape {arr.shape}")
        if arr.dtype.kind not in "iu":
            raise GraphValidationError(
                f"{name} must have an integer dtype (vertex ids), got "
                f"{arr.dtype}; cast with .astype(jnp.int32)")
    if colstarts.shape[0] != v + 1:
        raise GraphValidationError(
            f"colstarts must have n_vertices+1 = {v + 1} entries "
            f"(one past-the-end offset per vertex), got "
            f"{colstarts.shape[0]}")
    if colstarts.shape[0] and int(colstarts[0]) != 0:
        raise GraphValidationError(
            f"colstarts[0] must be 0 (offsets index into rows from the "
            f"start), got {int(colstarts[0])}")
    if np.any(np.diff(colstarts) < 0):
        bad = int(np.argmax(np.diff(colstarts) < 0))
        raise GraphValidationError(
            f"colstarts must be non-decreasing (adjacency extents "
            f"cannot have negative length); colstarts[{bad}]="
            f"{int(colstarts[bad])} > colstarts[{bad + 1}]="
            f"{int(colstarts[bad + 1])}")
    if int(colstarts[-1]) != e:
        raise GraphValidationError(
            f"colstarts[-1] ({int(colstarts[-1])}) must equal n_edges "
            f"({e}); the offsets and the declared edge count disagree")
    if rows.shape[0] < e:
        raise GraphValidationError(
            f"rows has {rows.shape[0]} entries but colstarts addresses "
            f"{e} edges; the adjacency array is truncated")
    if e:
        real = rows[:e]
        lo, hi = int(real.min()), int(real.max())
        if lo < 0 or hi >= v:
            bad_val = lo if lo < 0 else hi
            raise GraphValidationError(
                f"rows contains neighbor id {bad_val} outside "
                f"[0, n_vertices={v}); every real adjacency entry must "
                f"name an existing vertex (the sentinel {v} is only "
                f"legal in the padding tail)")
    if rows.shape[0] > e:
        pad = rows[e:]
        if np.any(pad < 0) or np.any(pad > v):
            raise GraphValidationError(
                f"rows padding tail contains ids outside [0, "
                f"sentinel={v}]; pad with the sentinel vertex id {v}")
    return csr


def traversed_edges(csr: Csr, reached: jax.Array) -> jax.Array:
    """Graph500 edge count for TEPS: sum of reached vertices' degrees / 2.

    ``reached`` is a (V,) bool mask of vertices in the BFS tree.
    Division by two converts directed (symmetrized) edges to the
    undirected count the Graph500 metric uses.
    """
    return (jnp.where(reached, csr.degrees(), 0)
            .sum(dtype=jnp.int32) // 2)
