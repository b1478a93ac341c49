"""BitmapCompressedFormat — word-compressed adjacency for the dense
regime.

The §3.3.1 bitmap idea applied to the *graph itself*: vertex u's
adjacency list becomes a (W,) uint32 row of the (V_pad, W) adjacency
bitmap — 1 bit per potential neighbor, the 32x compression the paper
uses for frontiers, now for edges.  Quadratic in V, so only small or
genuinely dense graphs qualify (the autotuner gates on a byte budget
and a density floor).

Where it wins: the bottom-up/dense regime the hybrid follow-up
[Paredes et al., arXiv:1704.02259] targets.  One layer is a pure
word-wise sweep ``adj & frontier`` — every unvisited vertex tests all
its neighbors against the frontier in W uint32 AND operations, with
**no gather, no scatter, no apportionment and no race at all** (the
discovered mask is computed densely, so updates are exact and the
restoration pass is unnecessary).  Each layer is effectively a
bitwise matrix-vector product, the densest possible use of the VPU.

The same sweep serves every engine mode: on the symmetrized Graph500
adjacency, "unvisited vertex with a neighbor in the frontier" is both
the bottom-up test and the top-down result.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmap as bm
from repro.core.csr import Csr, from_edges as csr_from_edges
from repro.core.rmat import EdgeList
from repro.formats.base import Footprint, GraphFormat, nbytes
from repro.formats.registry import register


@register
@jax.tree_util.register_pytree_node_class
class BitmapCompressedFormat(GraphFormat):
    name = "bitmap"
    supports_prefetch = False    # dense word sweep: no edge stream
    # the word sweep is plain XLA under every pipeline it builds
    tpu_pipelines = ("fused_gather", "materialized", "xla")
    # the word sweep stores bits, not neighbor ids — there is no
    # per-edge candidate stream to relax a semiring over, so the
    # algorithm portfolio (ISSUE 10) is rejected by `spec.validate`
    supported_semirings = ()

    def __init__(self, adj, deg, n_vertices: int, n_edges: int):
        self.adj = adj              # (V_pad, W) uint32 adjacency rows
        self.deg = deg              # (V,) int32
        self._n_vertices = int(n_vertices)
        self._n_edges = int(n_edges)

    # -- pytree ----------------------------------------------------------
    def tree_flatten(self):
        return ((self.adj, self.deg), (self._n_vertices, self._n_edges))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    # -- construction ----------------------------------------------------
    @classmethod
    def from_edges(cls, edges: EdgeList) -> "BitmapCompressedFormat":
        # no build options: unknown kwargs fail loudly at the call
        return cls.from_csr(csr_from_edges(edges))

    @classmethod
    def from_csr(cls, csr: Csr) -> "BitmapCompressedFormat":
        v = csr.n_vertices
        v_pad = csr.n_vertices_padded
        w = v_pad // bm.BITS_PER_WORD
        deg = np.asarray(csr.degrees(), np.int64)
        src = np.repeat(np.arange(v, dtype=np.int64), deg)
        dst = np.asarray(csr.rows[:csr.n_edges], np.int64)
        adj = np.zeros((v_pad, w), np.uint32)
        np.bitwise_or.at(
            adj, (src, dst >> bm.WORD_SHIFT),
            (np.uint32(1) << (dst & bm.WORD_MASK).astype(np.uint32)))
        return cls(jnp.asarray(adj),
                   jnp.asarray(deg, jnp.int32), v, csr.n_edges)

    # -- static geometry -------------------------------------------------
    @property
    def n_vertices(self) -> int:
        return self._n_vertices

    @property
    def n_edges(self) -> int:
        return self._n_edges

    # -- engine contract -------------------------------------------------
    def degrees(self) -> jax.Array:
        return self.deg

    def _sweep(self, frontier, visited, parent):
        """One exact dense layer (single root): word-wise adj & frontier.

        Parent of a discovered vertex is its lowest-id frontier
        neighbor (first set bit of the intersection) — deterministic,
        so no negative marking / restoration round is needed.
        """
        v = self._n_vertices
        v_pad = parent.shape[0]
        inter = self.adj & frontier[None, :]          # (V_pad, W)
        hit = jnp.any(inter != 0, axis=1)
        # membership stays packed: the visited test is a word AND on
        # the freshly packed hit bitmap (zero-conversion, ISSUE 4)
        new_words = bm.pack_bool(hit) & ~visited
        mask = bm.unpack_bool(new_words)
        # first set bit of the row: first nonzero word, then its lsb
        widx = jnp.argmax(inter != 0, axis=1).astype(jnp.int32)
        word = jnp.take_along_axis(inter, widx[:, None], axis=1)[:, 0]
        lsb = word & (~word + jnp.uint32(1))
        bit = jax.lax.population_count(lsb - jnp.uint32(1))
        parent_id = bm.bit2vertex(widx, bit.astype(jnp.int32))
        parent = jnp.where(mask, parent_id, parent)
        return new_words, visited | new_words, parent

    def _build_steps(self, spec) -> dict:
        # The dense word sweep is ZERO-conversion under the packed
        # engine: it consumes the packed frontier words directly
        # (``adj & frontier``) and emits packed output words — there
        # is no mask to compact and no stream to prefetch, so
        # ``spec.packed`` changes nothing here (and
        # ``spec.prefetch_depth > 0`` is rejected upstream by
        # `spec.validate(fmt)` — there is nothing to prefetch).
        from repro.core import engine
        vm = jax.vmap(self._sweep)

        # the dense sweep has no stream to materialize and no tiles to
        # skip, so both pipelines are the same step; one sweep per
        # root is its tile unit
        def step(frontier, visited, parent):
            out, vis, par = vm(frontier, visited, parent)
            return out, vis, par, engine.StepAux(
                jnp.int32(frontier.shape[0]), jnp.int32(0), 0)

        # one sweep is simultaneously the scalar, SIMD and bottom-up
        # flavour: the dense word AND *is* the bottom-up frontier test
        return {engine.MODE_SCALAR: step,
                engine.MODE_SIMD: step,
                engine.MODE_BOTTOMUP: step}

    # -- accounting ------------------------------------------------------
    def footprint(self) -> Footprint:
        return Footprint(self.name,
                         (("adj", nbytes(self.adj)),
                          ("degrees", nbytes(self.deg))))

    @property
    def edge_slots(self) -> int:
        # one sweep examines every potential edge, one bit per slot
        return int(self.adj.size) * bm.BITS_PER_WORD

    def layer_bytes(self) -> int:
        return nbytes(self.adj)       # the sweep streams the adj matrix

    def tile_bytes(self, tile: int) -> int:
        # StepAux reports one "tile" per root sweep: the whole matrix
        return nbytes(self.adj)

    def plan_bytes(self, tile: int, packed: bool = True) -> int:
        return 0                      # nothing to plan — no schedule

    def plan_mask_bytes(self, packed: bool = True) -> int:
        return 0                      # zero-conversion: no plan read
