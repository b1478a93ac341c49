"""CsrFormat — the existing §3.3.1 CSR as a registered GraphFormat.

A thin adapter around `core/csr.py`: the arrays and the §4.2 padding
convention are unchanged.  Since ISSUE 3 the default gather primitive
is the **fused in-kernel gather** (kernels/gather_expand.py): a
per-layer planning pass marks the rows-blocks the frontier's
adjacency touches and the kernel DMAs only those, recomputing
edge->owner with a VMEM binary search — HBM traffic proportional to
the live frontier.  ``pipeline="materialized"`` rebuilds the legacy
bitmap->apportion edge stream (`engine.edge_stream`, a full-E (u, v,
valid) HBM round trip per SIMD layer) for the ablation axis.  The
baseline every other format is measured against.
"""
from __future__ import annotations

import jax

from repro.core.csr import Csr, from_edges as csr_from_edges
from repro.core.rmat import EdgeList
from repro.formats.base import Footprint, GraphFormat, nbytes
from repro.formats.registry import register


@register
@jax.tree_util.register_pytree_node_class
class CsrFormat(GraphFormat):
    name = "csr"
    # the whole-layer megakernel (kernels/layer_fused.py) is built on
    # the CSR rows-block schedule; see GraphFormat.supports_megakernel
    supports_megakernel = True
    # the whole-traversal persistent kernel (ISSUE 9,
    # kernels/traversal_fused.py) keeps the in-kernel scalar arm
    # mode-blended into the same racy sweep, so both scalar
    # algorithms' reached sets are honored (the racy-parent tie-break
    # is tile-partition-determined either way)
    supports_persistent = True
    persistent_algorithms = ("simd", "nonsimd")
    # the semiring portfolio (ISSUE 10) rides the fused gather's
    # active-tile schedule with the scatter-min relax kernel
    # (kernels/gather_expand.py `gather_relax_batched`); see
    # GraphFormat.supported_semirings
    supported_semirings = ("sssp", "cc", "ksource_bfs")
    # on a TPU only the XLA expansion compiles; see
    # GraphFormat.tpu_pipelines and tests/test_tpu_compile.py
    tpu_pipelines = ("xla",)

    def __init__(self, colstarts, rows, n_vertices: int, n_edges: int):
        self.colstarts = colstarts
        self.rows = rows
        self._n_vertices = int(n_vertices)
        self._n_edges = int(n_edges)

    # -- pytree ----------------------------------------------------------
    def tree_flatten(self):
        return ((self.colstarts, self.rows),
                (self._n_vertices, self._n_edges))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0], leaves[1], *aux)

    # -- construction ----------------------------------------------------
    @classmethod
    def from_edges(cls, edges: EdgeList) -> "CsrFormat":
        # no build options: unknown kwargs fail loudly at the call
        return cls.from_csr(csr_from_edges(edges))

    @classmethod
    def from_csr(cls, csr: Csr) -> "CsrFormat":
        return cls(csr.colstarts, csr.rows, csr.n_vertices, csr.n_edges)

    def to_csr(self) -> Csr:
        return Csr(rows=self.rows, colstarts=self.colstarts,
                   n_vertices=self._n_vertices, n_edges=self._n_edges)

    def validate_structure(self) -> "CsrFormat":
        # memoized per instance: the data checks read the device
        # arrays back to host (O(E)), and the plan cache's hot path
        # re-plans the same format object many times
        if not getattr(self, "_structure_ok", False):
            from repro.core.csr import check_structure
            check_structure(self.to_csr())
            self._structure_ok = True
        return self

    # -- static geometry -------------------------------------------------
    @property
    def n_vertices(self) -> int:
        return self._n_vertices

    @property
    def n_edges(self) -> int:
        return self._n_edges

    @property
    def n_edges_padded(self) -> int:
        return int(self.rows.shape[0])

    # -- engine contract -------------------------------------------------
    def degrees(self) -> jax.Array:
        return self.colstarts[1:] - self.colstarts[:-1]

    def _build_steps(self, spec) -> dict:
        from repro.core import engine
        return engine._make_steps(self.colstarts, self.rows,
                                  self._n_vertices,
                                  self.n_vertices_padded,
                                  self.n_edges_padded, spec.algorithm,
                                  spec.tile, spec.pipeline, spec.packed,
                                  spec.prefetch_depth)

    def _build_semiring_step(self, spec, semiring):
        import jax.numpy as jnp

        from repro.core import engine
        from repro.kernels import ops
        tile = spec.tile
        rows_t = engine._pad_rows_to_tile(self.rows, self._n_vertices,
                                          tile)
        n_blocks = rows_t.shape[0] // tile
        v = self._n_vertices
        full_wl = jnp.arange(n_blocks, dtype=jnp.int32)

        def step(frontier, vals, dense):
            with ops.count_launches() as c:
                wl, na = engine.plan_active_tiles_batched(
                    self.colstarts, frontier, v, tile, n_blocks,
                    packed=spec.packed)
                # dense arm (CC endgame): skip the compacted schedule,
                # sweep every block — the planner still ran (its cost
                # is charged), but a near-full frontier makes the full
                # work-list the cheaper schedule
                wl = jnp.where(dense[:, None], full_wl[None], wl)
                na = jnp.where(dense, jnp.int32(n_blocks), na)
                new_vals, p_layer = ops.gather_relax_batched(
                    wl, na, rows_t, self.colstarts, frontier, vals,
                    n_vertices=v, tile=tile, unit=semiring.unit,
                    weighted=semiring.weighted)
            aux = engine.StepAux(na.sum(dtype=jnp.int32),
                                 jnp.int32(0), c.count)
            return new_vals, p_layer, aux

        return step

    def persistent_fits(self, n_roots: int, spec) -> bool:
        from repro.core import bitmap as bm
        from repro.core.engine import _pad_rows_to_tile
        from repro.kernels import ops
        rows_t = _pad_rows_to_tile(self.rows, self._n_vertices,
                                   spec.tile)
        return ops.persistent_fits(
            self.n_vertices_padded // bm.BITS_PER_WORD,
            self.n_vertices_padded, int(self.colstarts.shape[0]),
            spec.tile, int(n_roots), spec.max_layers,
            spec.prefetch_depth, int(rows_t.shape[0]) // spec.tile)

    def persistent_run(self, frontier, visited, parent, spec):
        from repro.core.engine import _pad_rows_to_tile
        from repro.kernels import ops
        rows_t = _pad_rows_to_tile(self.rows, self._n_vertices,
                                   spec.tile)
        return ops.traversal_fused_batched(
            rows_t, self.colstarts, frontier, visited, parent,
            n_vertices=self._n_vertices, tile=spec.tile,
            policy=spec.policy, max_layers=spec.max_layers,
            prefetch_depth=spec.prefetch_depth)

    def resolve_tile(self, tile: int | None) -> int:
        # CSR tiles the rows array: the fused pipeline's DMA block ==
        # the §4 prefetch distance.  The fused rule bottoms out at one
        # lane set (128) so small graphs still split into several
        # blocks for the active-tile schedule to skip; the hostloop
        # A/B driver keeps the legacy `_auto_tile` rule separately.
        # The auto choice reads the geometry-keyed affinity table
        # (formats/affinity.py) through the format instance.
        from repro.core import engine
        return engine._resolve_tile_csr(tile, self.n_edges_padded,
                                        fmt=self)

    # -- accounting ------------------------------------------------------
    def footprint(self) -> Footprint:
        return Footprint(self.name,
                         (("rows", nbytes(self.rows)),
                          ("colstarts", nbytes(self.colstarts))))

    @property
    def edge_slots(self) -> int:
        return self.n_edges_padded

    def layer_bytes(self) -> int:
        # the materialized pipeline WRITES the apportioned (u, v,
        # valid) stream to HBM and the kernel re-reads it: 2 x 3 words
        # x 4 B per slot per layer — the round trip the fused gather
        # eliminates
        return 2 * 3 * 4 * self.edge_slots

    def plan_bytes(self, tile: int, packed: bool = True) -> int:
        # the CSR planner also streams colstarts (degree marks)
        return (4 * (self.n_vertices + 1)
                + super().plan_bytes(tile, packed))
