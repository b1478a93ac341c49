"""Pallas TPU kernel: fused in-kernel CSR gather + active-tile schedule.

The frontier-proportional replacement for the materialized edge stream
(ISSUE 3).  `frontier_expand.py` consumes an apportioned ``(u, v,
valid)`` triple that a jnp pass writes to HBM and the kernel re-reads
— a layer touching 1% of the edges still moves ~3x E_pad words twice.
This kernel eliminates the round trip and makes the HBM traffic scale
with the live frontier:

* **in-kernel gather** — the kernel takes ``colstarts``/``rows``
  directly.  ``rows`` stays in HBM and is DMA'd one aligned
  *tile-sized block* per grid step (the Pallas indirection idiom:
  block-granular gathers through the BlockSpec index map).  The edge
  -> owner mapping that `engine.apportion` materialized is recomputed
  on the fly with a branchless binary search over the VMEM-resident
  ``colstarts`` — log2(V) VMEM gathers instead of an E_pad-word HBM
  stream.
* **scalar-prefetched active-tile scheduling** — a tiny on-device
  planning pass (`engine.plan_active_tiles`) marks which rows-blocks
  intersect the frontier's adjacency and compacts them into a
  *work-list*.  The work-list rides in scalar-prefetch memory: the
  BlockSpec index map reads ``worklist[t]`` to pick the block each
  grid step DMAs, entries past ``n_active`` are clamped to the last
  active block (an unchanged block index => Mosaic elides the repeated
  DMA) and a ``pl.when`` guard skips their compute.  A 1k-edge layer
  on a SCALE-22 graph therefore costs ~1 tile of traffic, not
  E_pad/tile tiles.  This is the TPU analog of the paper's §4
  prefetch-distance tuning: the *tile size* is the prefetch distance,
  the work-list replaces ``_mm_prefetch``.

Direction is a role swap on the same body (`_expand_tile`):

* top-down:  owner u gated by "u in frontier", neighbor v tested
  undiscovered, P[v] = u - |V| (the Listing 1 hot loop);
* bottom-up: the planner marks *unvisited* vertices' blocks, owner u
  tested undiscovered, neighbor v gated by "v in frontier",
  P[u] = v - |V| (the hybrid extension, arXiv:1704.02259).

Races and restoration are exactly the §3.3.2 story of the materialized
kernel: the word scatter may drop colliding bits, the negative P marks
let `restoration.py` repair them.

Since ISSUE 4 the kernel also offers a **manual double-buffered DMA
input pipeline** (``prefetch_depth`` > 0): ``rows`` stays in HBM (ANY
memory space) and the kernel itself issues ``make_async_copy`` for
tile ``t + depth`` while tile ``t`` computes, over ``depth + 1`` VMEM
buffers with per-slot DMA semaphores — the explicit-prefetch-distance
transcription of the paper's ``vprefetch`` tuning, where the
BlockSpec pipeline's automatic double buffering is the fixed
distance-1 special case.  The visited/frontier membership tests and
the output-queue scatter operate on packed uint32 words in VMEM
throughout (in-kernel packed test-and-set).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitmap import WORD_MASK, WORD_SHIFT
from repro.kernels.frontier_expand import _expand_tile
from repro.kernels import compiler_params

DEFAULT_TILE = 1024  # 8 sublanes x 128 lanes of int32


def _owner_search(colstarts, e_idx, n_entries: int):
    """Largest u with ``colstarts[u] <= e`` — branchless bit-lifting
    binary search (log2(V+1) VMEM gathers, no HBM traffic).

    This is the in-kernel inverse of the apportionment prefix-sum:
    edge position -> owning vertex.  ``colstarts[0] == 0 <= e`` holds
    for every slot, so the greedy bit descent is total; a result of
    ``n_entries - 1`` (== V) marks the sentinel-padded tail of rows.
    """
    u = jnp.zeros(e_idx.shape, jnp.int32)
    step = 1
    while step * 2 < n_entries:
        step *= 2
    while step:
        cand = u + step
        safe = jnp.clip(cand, 0, n_entries - 1)
        ok = (cand < n_entries) & (colstarts[safe] <= e_idx)
        u = jnp.where(ok, cand, u)
        step //= 2
    return u


def _gather_tile(n_vertices: int, tile: int, n_cs: int, bottom_up: bool,
                 blk, rows_blk, colstarts, frontier, vis, out, p):
    """One active tile: gather owners + run the shared hot-loop body."""
    e_idx = blk * tile + jnp.arange(tile, dtype=jnp.int32)
    u = _owner_search(colstarts, e_idx, n_cs)
    v = rows_blk
    valid = (u < n_vertices) & (v < n_vertices)
    # the role swap: the frontier-gated side goes through the
    # check_frontier test, the discovered side through the bitmap test
    nbr, cand = (v, u) if bottom_up else (u, v)
    return _expand_tile(n_vertices, True, nbr, cand, valid, frontier,
                        vis, out, p)


def _gather_kernel(n_vertices: int, tile: int, n_cs: int,
                   bottom_up: bool, wl_ref, na_ref, rows_ref, cs_ref,
                   frontier_ref, vis_ref, out0_ref, p0_ref, out_ref,
                   p_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():  # carry initial out/P into the accumulating outputs
        out_ref[...] = out0_ref[...]
        p_ref[...] = p0_ref[...]

    @pl.when(t < na_ref[0])
    def _work():  # inactive tiles: no DMA (clamped index), no compute
        out, p = _gather_tile(n_vertices, tile, n_cs, bottom_up,
                              wl_ref[t], rows_ref[...], cs_ref[...],
                              frontier_ref[...], vis_ref[...],
                              out_ref[...], p_ref[...])
        out_ref[...] = out
        p_ref[...] = p


def _gather_batched_kernel(n_vertices: int, tile: int, n_cs: int,
                           bottom_up: bool, wl_ref, na_ref, rows_ref,
                           cs_ref, frontier_ref, vis_ref, out0_ref,
                           p0_ref, out_ref, p_ref):
    """Batched variant: grid (roots, tiles); the adjacency is shared
    (no root axis on rows/colstarts), each root has its own work-list
    and accumulates into its own out/P rows."""
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = out0_ref[...]
        p_ref[...] = p0_ref[...]

    @pl.when(t < na_ref[b])
    def _work():
        out, p = _gather_tile(n_vertices, tile, n_cs, bottom_up,
                              wl_ref[b, t], rows_ref[...], cs_ref[...],
                              frontier_ref[0], vis_ref[0],
                              out_ref[0], p_ref[0])
        out_ref[...] = out[None]
        p_ref[...] = p[None]


def vmem_budget(n_words: int, v_pad: int, n_cs: int, tile: int,
                prefetch_depth: int = 0,
                n_blocks: int | None = None) -> int:
    """Bytes of VMEM pinned (bitmaps x3 + P x2 + colstarts + rows
    tile buffers — 2 for the automatic BlockSpec pipeline, the
    resolved ``depth + 1`` for the manual DMA pipeline).  The wrappers
    clamp ``prefetch_depth`` to the block count, so the budget charges
    the clamped depth too (ISSUE 9 satellite: budgets from the
    resolved spec only)."""
    depth = max(int(prefetch_depth), 0)
    if n_blocks is not None:
        depth = min(depth, max(int(n_blocks), 1))
    n_buf = max(2, depth + 1)
    return 4 * (3 * n_words + 2 * v_pad + n_cs) + n_buf * 4 * tile


def _dma_pipeline(rows_hbm, rows_buf, sems, wl, tile: int, depth: int,
                  n_blocks: int, t, warm, work):
    """The manual double-buffered input pipeline shared by the single
    and batched DMA kernels.

    At the first step of a root's tile sequence (``warm``) the DMAs
    for tiles 0..depth are started; at every step the DMA for tile
    ``t + depth`` is started (if it exists) before *waiting* on tile
    ``t``'s — so ``depth`` tiles are always in flight while the
    current tile computes (the §4 ``vprefetch`` distance, DMA-shaped).
    ``depth + 1`` buffer slots make the in-flight set disjoint from
    the compute slot.  The clamped work-list tail re-copies the last
    active block (cheap, and the tail's compute is skipped by the
    caller's ``pl.when`` guard).  ``work`` consumes the current
    tile's VMEM buffer."""
    n_buf = depth + 1

    def dma(step):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(wl(step) * tile, tile)],
            rows_buf.at[jax.lax.rem(step, n_buf)],
            sems.at[jax.lax.rem(step, n_buf)])

    @pl.when(warm)
    def _warmup():
        for k in range(min(depth, n_blocks)):
            dma(jnp.int32(k)).start()

    @pl.when(t + depth < n_blocks)
    def _ahead():
        dma(t + depth).start()

    dma(t).wait()
    work(rows_buf[jax.lax.rem(t, n_buf)])


def _gather_dma_kernel(n_vertices: int, tile: int, n_cs: int,
                       bottom_up: bool, depth: int, n_blocks: int,
                       wl_ref, na_ref, rows_ref, cs_ref, frontier_ref,
                       vis_ref, out0_ref, p0_ref, out_ref, p_ref,
                       rows_buf, sems):
    """`_gather_kernel` with the manual double-buffered input pipeline:
    ``rows`` stays in HBM (ANY memory space) and the kernel itself
    keeps ``depth`` tile DMAs in flight ahead of the compute tile."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = out0_ref[...]
        p_ref[...] = p0_ref[...]

    def work(rows_blk):
        @pl.when(t < na_ref[0])
        def _work():
            out, p = _gather_tile(n_vertices, tile, n_cs, bottom_up,
                                  wl_ref[t], rows_blk, cs_ref[...],
                                  frontier_ref[...], vis_ref[...],
                                  out_ref[...], p_ref[...])
            out_ref[...] = out
            p_ref[...] = p

    _dma_pipeline(rows_ref, rows_buf, sems, lambda s: wl_ref[s], tile,
                  depth, n_blocks, t, t == 0, work)


def _gather_dma_batched_kernel(n_vertices: int, tile: int, n_cs: int,
                               bottom_up: bool, depth: int,
                               n_blocks: int, wl_ref, na_ref, rows_ref,
                               cs_ref, frontier_ref, vis_ref, out0_ref,
                               p0_ref, out_ref, p_ref, rows_buf, sems):
    """Batched DMA variant: each root's tile sequence re-warms the
    pipeline at its first grid step (the grid stays sequential, so
    buffer slots hand over cleanly at root boundaries)."""
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = out0_ref[...]
        p_ref[...] = p0_ref[...]

    def work(rows_blk):
        @pl.when(t < na_ref[b])
        def _work():
            out, p = _gather_tile(n_vertices, tile, n_cs, bottom_up,
                                  wl_ref[b, t], rows_blk, cs_ref[...],
                                  frontier_ref[0], vis_ref[0],
                                  out_ref[0], p_ref[0])
            out_ref[...] = out[None]
            p_ref[...] = p[None]

    _dma_pipeline(rows_ref, rows_buf, sems, lambda s: wl_ref[b, s],
                  tile, depth, n_blocks, t, t == 0, work)


@functools.partial(jax.jit, static_argnames=("n_vertices", "tile",
                                             "bottom_up",
                                             "prefetch_depth",
                                             "interpret"))
def gather_expand(worklist, n_active, rows, colstarts, frontier,
                  visited, out_init, p_init, *, n_vertices: int,
                  tile: int = DEFAULT_TILE, bottom_up: bool = False,
                  prefetch_depth: int = 0, interpret: bool = True):
    """Fused gather-expand over the active rows-blocks of one layer.

    Args:
      worklist: (n_blocks,) int32 — block id each grid step DMAs.
        Active entries first; the tail must be clamped to the last
        active block (repeated index => the DMA is elided).
      n_active: (1,) int32 — live prefix length of ``worklist``.
      rows: (E_tiles,) int32 CSR adjacency, sentinel-padded, length a
        multiple of ``tile`` (pad once at build, NOT per layer).
      colstarts: (V + 1,) int32 — VMEM-resident for the owner search.
      frontier, visited, out_init: (W,) uint32 bitmaps.
      p_init: (V_pad,) int32 predecessor array.
      bottom_up: False = top-down gather, True = unvisited-adjacency
        sweep testing neighbors against the frontier.
      prefetch_depth: 0 = the BlockSpec pipeline (Mosaic's automatic
        double buffering); > 0 = the manual `make_async_copy` input
        pipeline with ``depth`` tile DMAs in flight ahead of the
        compute tile (``depth + 1`` VMEM buffers) — §4's prefetch
        distance as an explicit knob.
    Returns:
      (out, parent) after the racy expansion (restoration NOT applied)
      — the same contract as `frontier_expand.frontier_expand`.
    """
    n_slots = rows.shape[0]
    assert n_slots % tile == 0, "pad rows to the tile size at build"
    n_blocks = n_slots // tile
    assert worklist.shape[0] == n_blocks
    n_cs = colstarts.shape[0]
    n_words = visited.shape[0]
    v_pad = p_init.shape[0]

    whole = lambda n: pl.BlockSpec((n,), lambda t, wl, na: (0,))
    if prefetch_depth > 0:
        depth = min(int(prefetch_depth), n_blocks)
        rows_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
        scratch = [pltpu.VMEM((depth + 1, tile), jnp.int32),
                   pltpu.SemaphoreType.DMA((depth + 1,))]
        kernel = functools.partial(_gather_dma_kernel, n_vertices, tile,
                                   n_cs, bottom_up, depth, n_blocks)
    else:
        rows_spec = pl.BlockSpec((tile,), lambda t, wl, na: (wl[t],))
        scratch = []
        kernel = functools.partial(_gather_kernel, n_vertices, tile,
                                   n_cs, bottom_up)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks,),
        in_specs=[rows_spec,
                  whole(n_cs), whole(n_words), whole(n_words),
                  whole(n_words), whole(v_pad)],
        out_specs=[whole(n_words), whole(v_pad)],
        scratch_shapes=scratch,
    )
    out, parent = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_words,), jnp.uint32),
                   jax.ShapeDtypeStruct((v_pad,), jnp.int32)],
        compiler_params=compiler_params(
            # accumulating outputs => sequential grid on the core
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="bfs_gather_expand",
    )(worklist, n_active, rows, colstarts, frontier, visited, out_init,
      p_init)
    return out, parent


# ---------------------------------------------------------------------------
# Semiring relaxation (ISSUE 10): the same fused in-kernel gather, but
# the per-edge update is the (min, ⊗) pair of `algorithms/semiring.py`
# instead of the BFS bit test-and-set.  Two structural differences from
# the bitmap kernels above:
#
# * the scatter is a masked **scatter-min of values** — min is
#   commutative and associative, so the §3.3.2 word-collision race of
#   the BFS scatter does not exist here and no restoration pass is
#   needed.  Duplicate relaxations of the same target are benign by
#   algebra.
# * parents are resolved by a **second phase over the same tiles**
#   (grid (B, 2, tiles), phase-major sequential): phase 0 folds every
#   candidate into ``out_vals``; phase 1 re-walks the tiles and takes
#   the minimum source id among edges whose candidate EQUALS the
#   now-final value of an improved target.  The candidate is recomputed
#   from identical inputs with identical ops, so the float equality is
#   bitwise-exact, and "min u among optimal edges" makes the parent
#   tree deterministic without any restoration machinery.
#
# ⊗ arrives as data (``unit`` hop cost + optional synthetic
# ``edge_weight``), which is what lets one kernel serve sssp / cc /
# k-source BFS — see the Semiring table in algorithms/semiring.py.
# ---------------------------------------------------------------------------

#: parent-resolve scatter-min sentinel: larger than any vertex id
P_UNSET = jnp.iinfo(jnp.int32).max


def _relax_edges(n_vertices: int, tile: int, n_cs: int, unit: int,
                 weighted: bool, blk, rows_blk, colstarts, frontier,
                 vals):
    """Shared per-tile edge enumeration: gather owners, gate on the
    frontier, and form each edge's semiring candidate ``vals[u] ⊗ w``.
    Returns (u, v, mask, cand) for the phase-specific scatter."""
    from repro.algorithms.semiring import edge_weight

    e_idx = blk * tile + jnp.arange(tile, dtype=jnp.int32)
    u = _owner_search(colstarts, e_idx, n_cs)
    v = rows_blk
    valid = (u < n_vertices) & (v < n_vertices)
    uw = jnp.clip(u >> WORD_SHIFT, 0, frontier.shape[0] - 1)
    ub = (u & WORD_MASK).astype(jnp.uint32)
    in_front = ((frontier[uw] >> ub) & jnp.uint32(1)) != 0
    mask = valid & in_front
    u_val = vals[jnp.clip(u, 0, vals.shape[0] - 1)]
    if weighted:
        cand = u_val + edge_weight(u, v)
    elif unit:
        cand = u_val + jnp.asarray(unit, vals.dtype)
    else:
        cand = u_val
    return u, v, mask, cand


def _relax_scatter_vals(v_slots: int, u, v, mask, cand, out_vals):
    """Phase 0: fold candidates into the value row (masked scatter-min;
    out-of-mask lanes are dropped on the OOB sentinel index)."""
    idx = jnp.where(mask, v, v_slots)
    return out_vals.at[idx].min(cand, mode="drop")


def _relax_scatter_parents(v_slots: int, u, v, mask, cand, vals,
                           out_vals, p):
    """Phase 1: deterministic parent resolve against the finalized
    values — min source id among edges achieving the optimum, gated on
    strict improvement over the layer-start value."""
    v_clip = jnp.clip(v, 0, v_slots - 1)
    cur = out_vals[v_clip]
    win = mask & (cand == cur) & (cur < vals[v_clip])
    idx = jnp.where(win, v, v_slots)
    return p.at[idx].min(u, mode="drop")


def _relax_batched_kernel(n_vertices: int, tile: int, n_cs: int,
                          unit: int, weighted: bool, wl_ref, na_ref,
                          rows_ref, cs_ref, frontier_ref, vals_ref,
                          out_ref, p_ref):
    b = pl.program_id(0)
    ph = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when((ph == 0) & (t == 0))
    def _init():  # value row starts at the layer-start values
        out_ref[...] = vals_ref[...]
        p_ref[...] = jnp.full(p_ref.shape, P_UNSET, jnp.int32)

    @pl.when(t < na_ref[b])
    def _work():
        u, v, mask, cand = _relax_edges(
            n_vertices, tile, n_cs, unit, weighted, wl_ref[b, t],
            rows_ref[...], cs_ref[...], frontier_ref[0], vals_ref[0])
        v_slots = p_ref.shape[1]

        @pl.when(ph == 0)
        def _vals():
            out_ref[...] = _relax_scatter_vals(
                v_slots, u, v, mask, cand, out_ref[0])[None]

        @pl.when(ph == 1)
        def _parents():
            p_ref[...] = _relax_scatter_parents(
                v_slots, u, v, mask, cand, vals_ref[0], out_ref[0],
                p_ref[0])[None]


@functools.partial(jax.jit, static_argnames=("n_vertices", "tile",
                                             "unit", "weighted",
                                             "interpret"))
def gather_relax_batched(worklist, n_active, rows, colstarts, frontier,
                         vals, *, n_vertices: int,
                         tile: int = DEFAULT_TILE, unit: int = 0,
                         weighted: bool = False,
                         interpret: bool = True):
    """Multi-root semiring relaxation over the active rows-blocks of
    one layer (the (min, ⊗) generalization of `gather_expand_batched`).

    Args:
      worklist, n_active: (B, n_blocks) / (B,) — the same scalar-
        prefetched active-tile schedule as the BFS kernel (entries past
        ``n_active`` clamped, their DMA elided, compute skipped).
      rows, colstarts: the shared CSR adjacency (no root axis).
      frontier: (B, W) uint32 packed frontier bitmaps.
      vals: (B, V_pad) layer-start value rows (int32 or float32).
      unit, weighted: the ⊗ data — candidate along (u, v) is
        ``vals[u] + unit (+ edge_weight(u, v) if weighted)``.
    Returns:
      (out_vals, p_layer): the folded value rows and the per-layer
      parent scatter (``P_UNSET`` where no edge won; the driver merges
      it into the persistent parent array under the improved mask).
      No restoration pass exists or is needed — scatter-min commutes.
    """
    n_slots = rows.shape[0]
    assert n_slots % tile == 0, "pad rows to the tile size at build"
    n_blocks = n_slots // tile
    n_batch = worklist.shape[0]
    assert worklist.shape == (n_batch, n_blocks)
    n_cs = colstarts.shape[0]
    n_words = frontier.shape[1]
    v_pad = vals.shape[1]

    flat = lambda n: pl.BlockSpec((n,), lambda b, ph, t, wl, na: (0,))
    whole = lambda n: pl.BlockSpec((1, n),
                                   lambda b, ph, t, wl, na: (b, 0))
    rows_spec = pl.BlockSpec((tile,),
                             lambda b, ph, t, wl, na: (wl[b, t],))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # phase-major sequential: every phase-0 tile of a root lands
        # before its phase-1 tiles, so phase 1 reads finalized values
        grid=(n_batch, 2, n_blocks),
        in_specs=[rows_spec, flat(n_cs), whole(n_words), whole(v_pad)],
        out_specs=[whole(v_pad), whole(v_pad)],
    )
    out_vals, p_layer = pl.pallas_call(
        functools.partial(_relax_batched_kernel, n_vertices, tile,
                          n_cs, unit, weighted),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_batch, v_pad), vals.dtype),
                   jax.ShapeDtypeStruct((n_batch, v_pad), jnp.int32)],
        compiler_params=compiler_params(
            dimension_semantics=("arbitrary", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
        name="bfs_gather_relax_batched",
    )(worklist, n_active, rows, colstarts, frontier, vals)
    return out_vals, p_layer


@functools.partial(jax.jit, static_argnames=("n_vertices", "tile",
                                             "bottom_up",
                                             "prefetch_depth",
                                             "interpret"))
def gather_expand_batched(worklist, n_active, rows, colstarts, frontier,
                          visited, out_init, p_init, *, n_vertices: int,
                          tile: int = DEFAULT_TILE,
                          bottom_up: bool = False,
                          prefetch_depth: int = 0,
                          interpret: bool = True):
    """Multi-root fused gather-expand: one launch, B searches.

    ``worklist`` is (B, n_blocks) and ``n_active`` (B,) — each root
    schedules its own active tiles (a finished root has n_active == 0
    and costs nothing).  ``rows``/``colstarts`` carry no root axis
    (the layout is shared); bitmaps/P are (B, W) / (B, V_pad).  Grid
    is (B, n_tiles): roots parallel, tiles sequential.
    ``prefetch_depth`` > 0 selects the manual double-buffered DMA
    input pipeline (see `gather_expand`); the grid then stays fully
    sequential so buffer slots hand over cleanly at root boundaries.
    """
    n_slots = rows.shape[0]
    assert n_slots % tile == 0, "pad rows to the tile size at build"
    n_blocks = n_slots // tile
    n_batch = worklist.shape[0]
    assert worklist.shape == (n_batch, n_blocks)
    n_cs = colstarts.shape[0]
    n_words = visited.shape[1]
    v_pad = p_init.shape[1]

    flat = lambda n: pl.BlockSpec((n,), lambda b, t, wl, na: (0,))
    whole = lambda n: pl.BlockSpec((1, n), lambda b, t, wl, na: (b, 0))
    if prefetch_depth > 0:
        depth = min(int(prefetch_depth), n_blocks)
        rows_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
        scratch = [pltpu.VMEM((depth + 1, tile), jnp.int32),
                   pltpu.SemaphoreType.DMA((depth + 1,))]
        kernel = functools.partial(_gather_dma_batched_kernel,
                                   n_vertices, tile, n_cs, bottom_up,
                                   depth, n_blocks)
        semantics = ("arbitrary", "arbitrary")
    else:
        rows_spec = pl.BlockSpec((tile,),
                                 lambda b, t, wl, na: (wl[b, t],))
        scratch = []
        kernel = functools.partial(_gather_batched_kernel, n_vertices,
                                   tile, n_cs, bottom_up)
        semantics = ("parallel", "arbitrary")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_batch, n_blocks),
        in_specs=[rows_spec,
                  flat(n_cs), whole(n_words), whole(n_words),
                  whole(n_words), whole(v_pad)],
        out_specs=[whole(n_words), whole(v_pad)],
        scratch_shapes=scratch,
    )
    out, parent = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_batch, n_words), jnp.uint32),
                   jax.ShapeDtypeStruct((n_batch, v_pad), jnp.int32)],
        compiler_params=compiler_params(
            dimension_semantics=semantics),
        interpret=interpret,
        name="bfs_gather_expand_batched",
    )(worklist, n_active, rows, colstarts, frontier, visited, out_init,
      p_init)
    return out, parent
