"""SellFormat — SELL-C-σ adjacency (SlimSell) for wide-SIMD BFS.

SELL-C-σ [Kreutzer et al.; SlimSell, Besta et al. arXiv:2010.09913]:

* split each vertex's adjacency into **virtual rows** of at most
  ``max_width`` neighbors (row splitting — bounds the slice width by
  the chunk size instead of the hub degree on power-law graphs);
* sort virtual rows by length (descending) inside windows of **σ**
  rows — local sorting keeps similar-length rows adjacent without
  destroying locality globally;
* group the sorted rows into **slices** of C=128 (one slice = one TPU
  lane set, the AVX-512 register analogue of the paper's §4);
* store each slice's adjacency **column-major**, padded to the slice's
  own maximum row length — so one vector load reads one neighbor of
  128 different rows, fully aligned, and the padding cost is per-slice
  instead of the global ELLPACK max-degree.

We quantize slice widths to W_QUANT=8 columns so the storage unit is a
**slab**: an (8, 128) int32 block — exactly one aligned 8x128 vector
tile, the §4.2 alignment goal by construction.  Degree sorting (σ)
is what keeps the quantized padding small on skewed-degree graphs:
hub vertices share slices with hub vertices, so a slice of leaves is
1 slab wide instead of max-degree wide.

Traversal is the SpMV-style sweep of `kernels/sell_expand.py`.  Since
ISSUE 3 the sweep is **active-slab scheduled** under the default
``fused_gather`` pipeline: a per-layer planning pass tests each
slab's ``slab_rows`` against the frontier bitmap and compacts the
hits into a scalar-prefetched work-list, so a thin layer touches only
the slices holding frontier rows (O(frontier slices) slabs) instead
of all of nnz_sell — while still paying **no apportionment pass**
(CSR's per-layer compaction + prefix-sum over the edge stream) and no
gather irregularity in the stream itself.  ``materialized`` keeps the
full O(nnz_sell) sweep for the ablation axis; on skewed
small-diameter graphs (RMAT) almost all edges sit in 2-3 fat layers
anyway, so the full sweep's extra touched slots are small while its
aligned loads are strictly cheaper — the SlimSell argument.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csr import Csr, from_edges as csr_from_edges, round_up
from repro.core.rmat import EdgeList
from repro.formats.base import Footprint, GraphFormat, nbytes
from repro.formats.registry import register
from repro.kernels import interpret_mode, ops
from repro.kernels.sell_expand import SLICE_C, W_QUANT


@register
@jax.tree_util.register_pytree_node_class
class SellFormat(GraphFormat):
    name = "sell"
    # since ISSUE 9 the slab sweep fuses: `sell_layer_fused` rebuilds
    # the cols DMA around manual `make_async_copy`, so the kernel's
    # own t==0 slab plan (an SMEM work-list) drives the pipeline
    # instead of a scalar-prefetched BlockSpec index map that binds
    # before launch — the whole layer (plan + sweep + restoration) is
    # ONE Pallas call, and the whole traversal one launch under
    # pipeline="persistent"
    supports_megakernel = True
    # persistent is SIMD-only: the in-kernel layer loop has no dense
    # jnp arm, and SELL's "nonsimd" MODE_SCALAR semantics (Algorithm
    # 2 exact updates) need exactly that arm — `spec.validate`
    # rejects the combination
    supports_persistent = True
    persistent_algorithms = ("simd",)
    # the semiring portfolio (ISSUE 10) is the SlimSell SpMV reading
    # taken literally: the slab sweep over the (min, ⊗) pair
    # (kernels/sell_expand.py `sell_relax_batched`); see
    # GraphFormat.supported_semirings
    supported_semirings = ("sssp", "cc", "ksource_bfs")
    # on a TPU only the XLA expansion over the flattened slabs
    # compiles; see GraphFormat.tpu_pipelines
    tpu_pipelines = ("xla",)

    DEFAULT_SIGMA = 8 * SLICE_C   # SlimSell's typical local-sort window

    def __init__(self, cols, slab_rows, deg, n_vertices: int,
                 n_edges: int, sigma: int, nnz_stored: int):
        self.cols = cols            # (n_slabs, W_QUANT, C) int32
        self.slab_rows = slab_rows  # (n_slabs, C) int32
        self.deg = deg              # (V,) int32
        self._n_vertices = int(n_vertices)
        self._n_edges = int(n_edges)
        self.sigma = int(sigma)
        self.nnz_stored = int(nnz_stored)   # un-quantized padded slots

    # -- pytree ----------------------------------------------------------
    def tree_flatten(self):
        return ((self.cols, self.slab_rows, self.deg),
                (self._n_vertices, self._n_edges, self.sigma,
                 self.nnz_stored))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    # -- construction ----------------------------------------------------
    @classmethod
    def from_edges(cls, edges: EdgeList, *, sigma: int | None = None,
                   max_width: int = 64) -> "SellFormat":
        return cls.from_csr(csr_from_edges(edges), sigma=sigma,
                            max_width=max_width)

    @classmethod
    def from_csr(cls, csr: Csr, *, sigma: int | None = None,
                 max_width: int = 64) -> "SellFormat":
        """Row-split, degree-sort, slice, quantize and pack — Graph500
        kernel-2 preprocessing, vectorized in numpy on the host.

        **Row splitting**: a vertex of degree d becomes ceil(d /
        ``max_width``) *virtual rows* of at most ``max_width``
        neighbors each.  On a power-law graph this is what keeps the
        per-slice width (= max row length in the slice) bounded by
        ``max_width`` instead of by the hub degree — without it a
        single SCALE-12 RMAT hub pads its whole 128-lane slice to
        ~2000 columns and the sweep touches ~10x more slots than CSR.
        With splitting, padding is bounded by the W_QUANT quantum per
        virtual row, so stored slots ~= E + O(V).  The σ-sort then
        groups full-width chunks (zero padding) apart from the sorted
        tails (padding < W_QUANT per row).
        """
        c, wq = SLICE_C, W_QUANT
        assert max_width % wq == 0 and max_width > 0
        v = csr.n_vertices
        deg = np.asarray(csr.degrees(), dtype=np.int64)
        colstarts = np.asarray(csr.colstarts, dtype=np.int64)
        dst = np.asarray(csr.rows[:csr.n_edges], dtype=np.int32)

        # virtual row table: vertex id + chunk length per row
        n_full = deg // max_width
        tail = deg % max_width
        rows_per_vertex = n_full + (tail > 0)
        n_vrows = int(rows_per_vertex.sum())
        n_rows = round_up(max(n_vrows, 1), c)
        vrow_vertex = np.full(n_rows, v, np.int64)      # sentinel pad
        vrow_len = np.zeros(n_rows, np.int64)
        if n_vrows:
            vrow_vertex[:n_vrows] = np.repeat(
                np.arange(v, dtype=np.int64), rows_per_vertex)
            row_start = np.concatenate(
                [np.zeros(1, np.int64), np.cumsum(rows_per_vertex)])
            chunk = np.arange(n_vrows, dtype=np.int64) \
                - row_start[vrow_vertex[:n_vrows]]
            vrow_len[:n_vrows] = np.where(
                chunk < n_full[vrow_vertex[:n_vrows]], max_width,
                tail[vrow_vertex[:n_vrows]])

        if sigma is None:
            # auto σ reads the geometry-keyed affinity table like any
            # other tuned knob (affinity.sell.<geom>.sigma<N> rows)
            from repro.formats import affinity
            sig = int(affinity.resolve(csr, "sigma", cls.DEFAULT_SIGMA,
                                       fmt_name="sell"))
        else:
            sig = int(sigma)
        sig = min(round_up(max(sig, c), c), n_rows)

        # σ-windowed descending length sort (stable: ties keep order)
        order = np.arange(n_rows, dtype=np.int64)
        for w0 in range(0, n_rows, sig):
            sl = slice(w0, min(w0 + sig, n_rows))
            order[sl] = order[sl][np.argsort(-vrow_len[sl],
                                             kind="stable")]

        n_slices = n_rows // c
        widths = vrow_len[order].reshape(n_slices, c).max(axis=1)
        slab_counts = (widths + wq - 1) // wq            # quantized
        slab_base = np.concatenate(
            [np.zeros(1, np.int64), np.cumsum(slab_counts)])
        n_slabs = int(slab_base[-1])
        nnz_stored = int((widths * c).sum())

        rows_sorted = np.where(vrow_vertex[order] < v, vrow_vertex[order],
                               v).astype(np.int32)
        if n_slabs == 0:       # edgeless graph: one all-sentinel slab
            cols = np.full((1, wq, c), v, np.int32)
            slab_rows = np.full((1, c), v, np.int32)
        else:
            cols = np.full((n_slabs, wq, c), v, np.int32)
            slab_rows = np.repeat(rows_sorted.reshape(n_slices, c),
                                  slab_counts, axis=0)
            # scatter every real edge to its (slab, column, lane) slot
            if csr.n_edges:
                src = np.repeat(np.arange(v, dtype=np.int64), deg)
                j = np.arange(csr.n_edges, dtype=np.int64) \
                    - colstarts[src]                     # nth neighbor
                vrow = row_start[src] + j // max_width
                jj = j % max_width                       # col in chunk
                inv = np.empty(n_rows, np.int64)
                inv[order] = np.arange(n_rows, dtype=np.int64)
                pos = inv[vrow]
                slab_idx = slab_base[pos // c] + jj // wq
                cols[slab_idx, jj % wq, pos % c] = dst
        return cls(jnp.asarray(cols), jnp.asarray(slab_rows),
                   jnp.asarray(deg, jnp.int32), v, csr.n_edges,
                   sig, nnz_stored)

    # -- static geometry -------------------------------------------------
    @property
    def n_vertices(self) -> int:
        return self._n_vertices

    @property
    def n_edges(self) -> int:
        return self._n_edges

    @property
    def n_slabs(self) -> int:
        return int(self.cols.shape[0])

    @property
    def fill_ratio(self) -> float:
        """Real edges / stored (quantized) slots — the σ payoff."""
        return self._n_edges / max(self.edge_slots, 1)

    # -- engine contract -------------------------------------------------
    def degrees(self) -> jax.Array:
        return self.deg

    def _sweep_jnp(self, frontier, visited, parent, algorithm: str):
        """Pure-jnp reference sweep (one root) — the scalar-mode step
        and the oracle for the Pallas kernel.  SELL's gather (the
        flattened slab stream with the source-in-frontier lane mask)
        feeding the shared Algorithm 2/3 body."""
        from repro.core import bitmap as bm
        from repro.core.engine import expand_candidates
        v = self._n_vertices
        src, nbr = self._slot_pairs()
        in_front = bm.test_bits(frontier, src) & (src < v)
        valid = in_front & (nbr < v)
        return expand_candidates(src, nbr, valid, frontier, visited,
                                 parent, v, algorithm)

    def _slot_pairs(self):
        """The slabs flattened to one (owner row, neighbor) stream;
        padding lanes carry the sentinel in both."""
        src = jnp.broadcast_to(self.slab_rows[:, None, :],
                               self.cols.shape).reshape(-1)
        return src, self.cols.reshape(-1)

    def _plan_slab_steps(self, active_words, slabs_per_step: int,
                         n_steps: int):
        """Active slab-group work-list for one root (ISSUE 3/4).

        ``active_words`` is a packed membership bitmap over vertices:
        a slab group is active iff any of its lanes' owning rows has
        its bit set — exactly the kernel's gating/discovery mask for
        that direction, so skipping inactive groups changes nothing.
        Top-down passes the *frontier* (slabs without frontier rows
        are skipped); bottom-up passes ``~visited`` (fully-visited
        slices drop out — the late-search early exit).  Sentinel
        (padding) rows are never members, so empty/padding slabs are
        excluded by the same test instead of being re-DMA'd through
        the clamped tail.  The clamp-to-last-active tail contract
        lives in `engine.compact_worklist`."""
        from repro.core import bitmap as bm
        from repro.core.engine import compact_worklist
        v = self._n_vertices
        rows = self.slab_rows
        active = (bm.test_bits(active_words, rows)
                  & (rows < v)).any(axis=1)
        pad = n_steps * slabs_per_step - active.shape[0]
        if pad:       # ops-level sentinel slabs are never active
            active = jnp.concatenate(
                [active, jnp.zeros((pad,), bool)])
        act_step = active.reshape(n_steps, slabs_per_step).any(axis=1)
        return compact_worklist(act_step, n_steps)

    def _build_semiring_step(self, spec, semiring):
        from repro.core import engine
        tile = spec.tile                       # slabs per step
        n_steps = -(-self.n_slabs // tile)
        v = self._n_vertices
        full_wl = jnp.arange(n_steps, dtype=jnp.int32)

        def step(frontier, vals, dense):
            with ops.count_launches() as c:
                wl, na = jax.vmap(
                    lambda a: self._plan_slab_steps(a, tile, n_steps)
                )(frontier)
                # dense arm (CC endgame): a near-full frontier sweeps
                # the full slab work-list instead of the compaction
                wl = jnp.where(dense[:, None], full_wl[None], wl)
                na = jnp.where(dense, jnp.int32(n_steps), na)
                new_vals, p_layer = ops.sell_relax_batched(
                    self.cols, self.slab_rows, wl, na, frontier, vals,
                    n_vertices=v, slabs_per_step=tile,
                    unit=semiring.unit, weighted=semiring.weighted)
            aux = engine.StepAux(na.sum(dtype=jnp.int32),
                                 jnp.int32(0), c.count)
            return new_vals, p_layer, aux

        return step

    def _build_steps(self, spec) -> dict:
        # SELL's planning is word-native already (a packed-bitmap
        # membership test over slab_rows), so ``spec.packed`` does
        # not change the step bodies — both parity arms run the same
        # packed-word plan.
        from repro.core import bitmap as bm
        from repro.core import engine
        algorithm, tile = spec.algorithm, spec.tile
        prefetch_depth = spec.prefetch_depth
        v = self._n_vertices
        n_steps = -(-self.n_slabs // tile)
        if spec.pipeline == "xla":
            return engine.make_xla_steps(*self._slot_pairs(), v,
                                         algorithm, n_steps)
        # the persistent pipeline's PER-LAYER steps (the serve tier's
        # tick path) are the megakernel steps — whole-traversal
        # queries bypass steps entirely via `persistent_run`
        mega = spec.pipeline in ("megakernel", "persistent")
        if mega:
            n_words = self.n_vertices_padded // bm.BITS_PER_WORD
            if not ops.sell_megakernel_fits(n_words,
                                            self.n_vertices_padded,
                                            self.n_slabs, tile,
                                            prefetch_depth):
                # observable degrade, mirroring engine._make_steps'
                # CSR megakernel arm: past the VMEM budget the layer
                # traverses via the unfused active-slab steps
                engine._record_degrade(
                    "vmem_fallback",
                    reason=ops.budget_detail(
                        f"sell_megakernel(v_pad="
                        f"{self.n_vertices_padded}, "
                        f"slabs={self.n_slabs}, spp={tile}, "
                        f"depth={prefetch_depth})",
                        ops.sell_megakernel_budget(
                            n_words, self.n_vertices_padded,
                            self.n_slabs, tile, prefetch_depth)),
                    fallback="pipeline='fused_gather' unfused slab "
                             "steps (3 launches/layer instead of 1)")
                mega = False
        fused = (not mega) and spec.pipeline != "materialized"

        def make_kernel_step(bottom_up: bool):
            def kernel_step(frontier, visited, parent):
                with ops.count_launches() as c:
                    kw = {}
                    if fused:
                        # the planning bitmap is the direction's
                        # *discovery-relevant* membership set: frontier
                        # rows (top-down) vs unvisited rows (bottom-up)
                        active = ~visited if bottom_up else frontier
                        wl, na = jax.vmap(
                            lambda a: self._plan_slab_steps(
                                a, tile, n_steps))(active)
                        kw = dict(worklist=wl, n_active=na)
                        tiles = na.sum(dtype=jnp.int32)
                    else:
                        tiles = jnp.int32(frontier.shape[0] * n_steps)
                    out_racy, p_racy = ops.sell_batched(
                        self.cols, self.slab_rows, frontier, visited,
                        jnp.zeros_like(frontier), parent, n_vertices=v,
                        slabs_per_step=tile, bottom_up=bottom_up,
                        prefetch_depth=prefetch_depth, **kw)
                    p_fixed, delta = ops.restore(p_racy, n_vertices=v)
                return (out_racy | delta, visited | delta, p_fixed,
                        engine.StepAux(tiles, jnp.int32(0), c.count))
            return kernel_step

        def make_mega_step(bottom_up: bool):
            # ONE Pallas call per layer: in-kernel slab plan + manual
            # cols DMA + sweep + restoration (kernels/sell_expand.py)
            def mega_step(frontier, visited, parent):
                with ops.count_launches() as c:
                    out, p_fixed, na = ops.sell_layer_fused_batched(
                        self.cols, self.slab_rows, frontier, visited,
                        parent, n_vertices=v, slabs_per_step=tile,
                        bottom_up=bottom_up,
                        prefetch_depth=prefetch_depth)
                return (out, visited | out, p_fixed,
                        engine.StepAux(na.sum(dtype=jnp.int32),
                                       jnp.int32(0), c.count))
            return mega_step

        make_step = make_mega_step if mega else make_kernel_step
        kernel_step = make_step(bottom_up=False)

        def jnp_step(frontier, visited, parent):
            out, vis, par = jax.vmap(
                lambda f, vi, p: self._sweep_jnp(f, vi, p,
                                                 algorithm))(
                frontier, visited, parent)
            return out, vis, par, engine.StepAux(
                jnp.int32(frontier.shape[0] * n_steps), jnp.int32(0), 0)

        # MODE_BOTTOMUP is a true role swap since ISSUE 4: the kernel
        # discovers *rows* gated on "neighbor in frontier", so its
        # planner schedules only the slabs of unvisited rows — on the
        # fat late layers of a hybrid search that is a handful of
        # slabs instead of every slab holding frontier rows.
        # MODE_SCALAR maps to the top-down kernel — SELL has no
        # cheaper "scalar" gather, so a thin layer costs the same
        # (active-scheduled) sweep either way — except under
        # algorithm="nonsimd", whose Algorithm-2 exact-update
        # semantics need the dense jnp path.
        scalar_step = kernel_step if algorithm == "simd" else jnp_step
        return {engine.MODE_SCALAR: scalar_step,
                engine.MODE_SIMD: kernel_step,
                engine.MODE_BOTTOMUP: make_step(bottom_up=True)}

    def persistent_fits(self, n_roots: int, spec) -> bool:
        from repro.core import bitmap as bm
        return ops.sell_persistent_fits(
            self.n_vertices_padded // bm.BITS_PER_WORD,
            self.n_vertices_padded, self.n_slabs, spec.tile,
            int(n_roots), spec.max_layers, spec.prefetch_depth)

    def persistent_run(self, frontier, visited, parent, spec):
        return ops.sell_traversal_fused_batched(
            self.cols, self.slab_rows, self.deg, frontier, visited,
            parent, n_vertices=self._n_vertices,
            slabs_per_step=spec.tile, policy=spec.policy,
            max_layers=spec.max_layers,
            prefetch_depth=spec.prefetch_depth)

    def resolve_tile(self, tile: int | None) -> int:
        """SELL's tile is *slabs per grid step*; the slice geometry
        fixes the aligned unit, so on TPU the grid is literally one
        slab (= one slice column-group) per step.  Interpret mode
        unrolls the grid at trace time, so clamp to <=32 steps there
        (the engine's `_auto_tile` rule, in slab units)."""
        n_slabs = self.n_slabs
        interpret = interpret_mode()
        floor = max(1, -(-n_slabs // 32)) if interpret else 1
        if tile is None:
            return floor
        return max(int(tile), floor) if interpret else max(1, int(tile))

    # -- accounting ------------------------------------------------------
    def footprint(self) -> Footprint:
        return Footprint(self.name,
                         (("cols", nbytes(self.cols)),
                          ("slab_rows", nbytes(self.slab_rows)),
                          ("degrees", nbytes(self.deg))))

    @property
    def edge_slots(self) -> int:
        return self.n_slabs * W_QUANT * SLICE_C

    def layer_bytes(self) -> int:
        # one full (materialized) sweep streams every cols slab + its
        # slab_rows ids
        return 4 * self.n_slabs * (W_QUANT + 1) * SLICE_C

    def tile_bytes(self, tile: int) -> int:
        # one active slab group: `tile` slabs of cols + slab_rows
        return 4 * tile * (W_QUANT + 1) * SLICE_C

    def plan_mask_bytes(self, packed: bool = True) -> int:
        # SELL's planner is word-native in BOTH arms (`make_steps`
        # ignores ``packed``): the membership test gathers from the
        # packed bitmap either way, so the dense-mask model would
        # charge bytes no SELL code path ever moves
        return self.n_vertices_padded // 8

    def plan_bytes(self, tile: int, packed: bool = True) -> int:
        # the slab planner scans every slab's row ids, gathers
        # membership from the packed bitmap, + the work-list round
        # trip
        n_steps = -(-self.n_slabs // max(tile, 1))
        return (4 * self.n_slabs * SLICE_C
                + self.plan_mask_bytes(packed) + 2 * 4 * n_steps)
