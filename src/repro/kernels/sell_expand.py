"""Pallas TPU kernel: SELL-C-σ slice expansion (SlimSell traversal).

The format-specialized counterpart of `frontier_expand.py`.  The CSR
kernel consumes an *apportioned* edge stream built on the host side of
the layer (compaction + prefix-sum over the frontier); the SELL kernel
instead sweeps the SELL-C-σ adjacency itself, SpMV-style [SlimSell,
arXiv:2010.09913]: every layer touches every stored slot, but every
load is a fully aligned slab and the frontier test is a lane mask —
no gather irregularity in the stream, no apportionment pass at all.

Layout (built in formats/sell.py):

* vertices are degree-sorted within σ-windows and grouped into
  **slices** of C=128 rows (one slice row set = one TPU lane set);
* each slice stores its adjacency column-major, padded to the slice's
  own width rounded up to W_Q=8 columns — so the unit of storage is a
  **slab**: an (8, 128) int32 block, exactly one aligned 8x128 vector
  tile.  ``cols[slab, q, lane]`` is a neighbor id (sentinel V pads),
  ``slab_rows[slab, lane]`` the owning vertex id.

Grid = slices (``slabs_per_step`` slabs per grid step; on TPU one
step per slab, i.e. literally one slice column-group).  Since ISSUE 3
the grid is **active-step scheduled**: a scalar-prefetched work-list
(`formats.sell.SellFormat` plans it from the frontier x ``slab_rows``
membership test) picks which slab group each grid step DMAs; entries
past the live count are clamped to the last active group (unchanged
block index => Mosaic elides the repeated DMA) and a ``pl.when``
guard skips their compute — so a thin layer sweeps only the slices
that actually hold frontier rows instead of all of nnz_sell.  Passing
the identity work-list recovers the full SpMV sweep (the
``materialized`` pipeline of the ablation axis).  Per step:

  1. load the slab's neighbor ids + row ids  (aligned vector loads —
     the §4.2 alignment goal with zero peel/remainder handling)
  2. lane mask: row in frontier  AND  neighbor unvisited  AND  not
     sentinel — masks replace the paper's peel/remainder loops exactly
     as §4.2's padding does
  3. masked scatter P[nbr] = row - |V|   (negative mark, §3.3.2)
  4. masked racy word scatter out |= bit (Fig. 6 race; restoration
     repairs)

Because the (row, nbr) direction of the test is symmetric in the
symmetrized Graph500 adjacency, the same sweep serves top-down and
bottom-up: "row in frontier, neighbor undiscovered" is exactly the
bottom-up "candidate unvisited, parent in frontier" read along the
reverse edge.  `formats/sell.py` therefore maps both engine modes
onto this one kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitmap import WORD_MASK, WORD_SHIFT
from repro.kernels.gather_expand import (P_UNSET, _dma_pipeline,
                                         _relax_scatter_parents,
                                         _relax_scatter_vals)
from repro.kernels.layer_fused import _restore_in_kernel
from repro.kernels import compiler_params

SLICE_C = 128   # rows per slice = TPU vector lane count (csr.LANES)
W_QUANT = 8     # columns per slab: 8x128 int32 = one aligned tile


def _sell_tile(n_vertices: int, bottom_up: bool, cols, rows, frontier,
               vis, out, p):
    """One grid step of the sweep on loaded VMEM values.

    cols: (S, W_QUANT, C) neighbor ids; rows: (S, C) owning vertex ids.
    Returns the updated (out, p) for this step's writes.

    ``bottom_up`` swaps the roles on the symmetrized adjacency: the
    top-down sweep gates on "row in frontier" and discovers the
    *neighbor*; the bottom-up sweep gates on "neighbor in frontier"
    and discovers the *row* — the hybrid's "unvisited candidate scans
    its parents" read, which is what lets the planner schedule only
    the slabs of *unvisited* rows late in the search (fully-visited
    slices drop out entirely)."""
    nbr = cols
    src = jnp.broadcast_to(rows[:, None, :], cols.shape)
    # the frontier-gated side vs the discovered side (role swap)
    gate, disc = (nbr, src) if bottom_up else (src, nbr)

    # lane mask 1: gated side in the frontier
    sw = jnp.clip(gate >> WORD_SHIFT, 0, frontier.shape[0] - 1)
    sb = (gate & WORD_MASK).astype(jnp.uint32)
    in_front = (frontier[sw] >> sb) & jnp.uint32(1) != 0

    # lane mask 2: discovered side undiscovered; sentinels filter out
    word = disc >> WORD_SHIFT
    bit = (disc & WORD_MASK).astype(jnp.uint32)
    bits = jnp.uint32(1) << bit
    w_clip = jnp.clip(word, 0, out.shape[0] - 1)
    out_words = out[w_clip]
    undiscovered = ((vis[w_clip] | out_words) & bits) == 0

    mask = (in_front & undiscovered
            & (nbr < n_vertices) & (src < n_vertices))

    # masked scatter of P (negative marking) — benign duplicate race
    p_idx = jnp.where(mask, disc, p.shape[0])
    new_p = p.at[p_idx].set(gate - n_vertices, mode="drop")

    # masked racy word scatter of the output queue (Fig. 6 race)
    new_words = out_words | bits
    w_idx = jnp.where(mask, word, out.shape[0])
    new_out = out.at[w_idx].set(new_words, mode="drop")
    return new_out, new_p


def _sell_kernel(n_vertices: int, bottom_up: bool, wl_ref, na_ref,
                 cols_ref, rows_ref, frontier_ref, vis_ref, out0_ref,
                 p0_ref, out_ref, p_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():  # carry initial out/P into the accumulating outputs
        out_ref[...] = out0_ref[...]
        p_ref[...] = p0_ref[...]

    @pl.when(t < na_ref[0])
    def _work():  # inactive steps: no DMA (clamped index), no compute
        out, p = _sell_tile(n_vertices, bottom_up, cols_ref[...],
                            rows_ref[...], frontier_ref[...],
                            vis_ref[...], out_ref[...], p_ref[...])
        out_ref[...] = out
        p_ref[...] = p


def _sell_batched_kernel(n_vertices: int, bottom_up: bool, wl_ref,
                         na_ref, cols_ref, rows_ref, frontier_ref,
                         vis_ref, out0_ref, p0_ref, out_ref, p_ref):
    """Batched variant: grid (roots, slice steps).  The adjacency slabs
    are root-independent (shared blocks); bitmaps/P carry a leading
    size-1 root axis, each root accumulating into its own rows; each
    root schedules its own active-slab work-list."""
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = out0_ref[...]
        p_ref[...] = p0_ref[...]

    @pl.when(t < na_ref[b])
    def _work():
        out, p = _sell_tile(n_vertices, bottom_up, cols_ref[...],
                            rows_ref[...], frontier_ref[0], vis_ref[0],
                            out_ref[0], p_ref[0])
        out_ref[...] = out[None]
        p_ref[...] = p[None]


def _sell_dma_pipeline(cols_hbm, rows_hbm, cols_buf, rows_buf, sems,
                       wl, spp: int, depth: int, n_steps: int, t, warm,
                       work):
    """Manual double-buffered input pipeline over BOTH slab arrays.

    Per step two DMAs (cols slab group + its slab_rows) share a slot;
    ``depth`` steps stay in flight ahead of the compute step, exactly
    the gather kernel's pipeline shape (see
    `gather_expand._dma_pipeline`)."""
    n_buf = depth + 1

    def dmas(step):
        slot = jax.lax.rem(step, n_buf)
        g = wl(step)
        return (pltpu.make_async_copy(
                    cols_hbm.at[pl.ds(g * spp, spp)], cols_buf.at[slot],
                    sems.at[0, slot]),
                pltpu.make_async_copy(
                    rows_hbm.at[pl.ds(g * spp, spp)], rows_buf.at[slot],
                    sems.at[1, slot]))

    @pl.when(warm)
    def _warmup():
        for k in range(min(depth, n_steps)):
            for d in dmas(jnp.int32(k)):
                d.start()

    @pl.when(t + depth < n_steps)
    def _ahead():
        for d in dmas(t + depth):
            d.start()

    for d in dmas(t):
        d.wait()
    slot = jax.lax.rem(t, n_buf)
    work(cols_buf[slot], rows_buf[slot])


def _sell_dma_kernel(n_vertices: int, bottom_up: bool, spp: int,
                     depth: int, n_steps: int, wl_ref, na_ref,
                     cols_ref, rows_ref, frontier_ref, vis_ref,
                     out0_ref, p0_ref, out_ref, p_ref, cols_buf,
                     rows_buf, sems):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = out0_ref[...]
        p_ref[...] = p0_ref[...]

    def work(cols_blk, rows_blk):
        @pl.when(t < na_ref[0])
        def _work():
            out, p = _sell_tile(n_vertices, bottom_up, cols_blk,
                                rows_blk, frontier_ref[...],
                                vis_ref[...], out_ref[...], p_ref[...])
            out_ref[...] = out
            p_ref[...] = p

    _sell_dma_pipeline(cols_ref, rows_ref, cols_buf, rows_buf, sems,
                       lambda s: wl_ref[s], spp, depth, n_steps, t,
                       t == 0, work)


def _sell_dma_batched_kernel(n_vertices: int, bottom_up: bool,
                             spp: int, depth: int, n_steps: int,
                             wl_ref, na_ref, cols_ref, rows_ref,
                             frontier_ref, vis_ref, out0_ref, p0_ref,
                             out_ref, p_ref, cols_buf, rows_buf, sems):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = out0_ref[...]
        p_ref[...] = p0_ref[...]

    def work(cols_blk, rows_blk):
        @pl.when(t < na_ref[b])
        def _work():
            out, p = _sell_tile(n_vertices, bottom_up, cols_blk,
                                rows_blk, frontier_ref[0], vis_ref[0],
                                out_ref[0], p_ref[0])
            out_ref[...] = out[None]
            p_ref[...] = p[None]

    _sell_dma_pipeline(cols_ref, rows_ref, cols_buf, rows_buf, sems,
                       lambda s: wl_ref[b, s], spp, depth, n_steps, t,
                       t == 0, work)


def vmem_budget(n_words: int, v_pad: int, slabs_per_step: int,
                prefetch_depth: int = 0, n_steps: int | None = None) -> int:
    """Bytes of VMEM pinned (bitmaps x4 + P x2 + slab buffers — 2 for
    the automatic BlockSpec pipeline, ``depth + 1`` for the manual DMA
    pipeline).  ``depth`` is the *resolved* pipeline depth: the
    wrappers clamp ``prefetch_depth`` to the step count, so the budget
    must too — charging the unclamped depth rejects shallow sweeps
    that the kernel would actually run with fewer buffers (ISSUE 9
    satellite: budgets compute from the resolved spec only)."""
    slab = slabs_per_step * (W_QUANT + 1) * SLICE_C * 4
    depth = max(int(prefetch_depth), 0)
    if n_steps is not None:
        depth = min(depth, max(int(n_steps), 1))
    return 4 * (4 * n_words + 2 * v_pad) + max(2, depth + 1) * slab


@functools.partial(jax.jit, static_argnames=("n_vertices",
                                             "slabs_per_step",
                                             "bottom_up",
                                             "prefetch_depth",
                                             "interpret"))
def sell_expand(cols, slab_rows, worklist, n_active, frontier, visited,
                out_init, p_init, *, n_vertices: int,
                slabs_per_step: int = 1, bottom_up: bool = False,
                prefetch_depth: int = 0, interpret: bool = True):
    """Single-root SELL sweep over the active slab groups.

    Args:
      cols: (n_slabs, W_QUANT, C) int32 neighbor slabs (sentinel-padded;
        n_slabs must be a multiple of ``slabs_per_step``).
      slab_rows: (n_slabs, C) int32 owning vertex ids per slab.
      worklist: (n_steps,) int32 slab-group id per grid step, active
        prefix first, tail clamped to the last active group.
        ``jnp.arange(n_steps)`` + ``n_active == n_steps`` recovers the
        full sweep.
      n_active: (1,) int32 live prefix length of ``worklist``.
      frontier, visited, out_init: (W,) uint32 bitmaps.
      p_init: (V_pad,) int32 predecessor array.
    Returns:
      (out, parent) after the racy sweep (restoration NOT applied) —
      the same contract as `frontier_expand.frontier_expand`.
    """
    n_slabs = cols.shape[0]
    assert n_slabs % slabs_per_step == 0, \
        "pad the slab count to the step size"
    n_steps = n_slabs // slabs_per_step
    assert worklist.shape[0] == n_steps
    n_words = visited.shape[0]
    v_pad = p_init.shape[0]

    whole = lambda n: pl.BlockSpec((n,), lambda t, wl, na: (0,))
    if prefetch_depth > 0:
        depth = min(int(prefetch_depth), n_steps)
        any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
        cols_spec, rows_spec = any_spec, any_spec
        scratch = [pltpu.VMEM((depth + 1, slabs_per_step, W_QUANT,
                               SLICE_C), jnp.int32),
                   pltpu.VMEM((depth + 1, slabs_per_step, SLICE_C),
                              jnp.int32),
                   pltpu.SemaphoreType.DMA((2, depth + 1))]
        kernel = functools.partial(_sell_dma_kernel, n_vertices,
                                   bottom_up, slabs_per_step, depth,
                                   n_steps)
    else:
        cols_spec = pl.BlockSpec((slabs_per_step, W_QUANT, SLICE_C),
                                 lambda t, wl, na: (wl[t], 0, 0))
        rows_spec = pl.BlockSpec((slabs_per_step, SLICE_C),
                                 lambda t, wl, na: (wl[t], 0))
        scratch = []
        kernel = functools.partial(_sell_kernel, n_vertices, bottom_up)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_steps,),
        in_specs=[cols_spec, rows_spec, whole(n_words), whole(n_words),
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
        name="bfs_sell_expand",
    )(worklist, n_active, cols, slab_rows, frontier, visited, out_init,
      p_init)
    return out, parent


@functools.partial(jax.jit, static_argnames=("n_vertices",
                                             "slabs_per_step",
                                             "bottom_up",
                                             "prefetch_depth",
                                             "interpret"))
def sell_expand_batched(cols, slab_rows, worklist, n_active, frontier,
                        visited, out_init, p_init, *, n_vertices: int,
                        slabs_per_step: int = 1, bottom_up: bool = False,
                        prefetch_depth: int = 0,
                        interpret: bool = True):
    """Multi-root SELL sweep: one launch expands B independent searches.

    The adjacency (cols, slab_rows) has NO root axis — the layout is
    shared; bitmaps/P carry a leading (B,) and so do ``worklist``
    ((B, n_steps)) and ``n_active`` ((B,)) — a finished root has
    ``n_active == 0`` and costs nothing.  Grid is (B, slice steps):
    the root axis is embarrassingly parallel, the slice axis stays
    sequential so later slabs observe earlier slabs' updates.
    """
    n_slabs = cols.shape[0]
    assert n_slabs % slabs_per_step == 0, \
        "pad the slab count to the step size"
    n_steps = n_slabs // slabs_per_step
    n_batch, n_words = visited.shape
    assert worklist.shape == (n_batch, n_steps)
    v_pad = p_init.shape[1]

    whole = lambda n: pl.BlockSpec((1, n), lambda b, t, wl, na: (b, 0))
    if prefetch_depth > 0:
        depth = min(int(prefetch_depth), n_steps)
        any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
        cols_spec, rows_spec = any_spec, any_spec
        scratch = [pltpu.VMEM((depth + 1, slabs_per_step, W_QUANT,
                               SLICE_C), jnp.int32),
                   pltpu.VMEM((depth + 1, slabs_per_step, SLICE_C),
                              jnp.int32),
                   pltpu.SemaphoreType.DMA((2, depth + 1))]
        kernel = functools.partial(_sell_dma_batched_kernel, n_vertices,
                                   bottom_up, slabs_per_step, depth,
                                   n_steps)
        semantics = ("arbitrary", "arbitrary")
    else:
        cols_spec = pl.BlockSpec((slabs_per_step, W_QUANT, SLICE_C),
                                 lambda b, t, wl, na: (wl[b, t], 0, 0))
        rows_spec = pl.BlockSpec((slabs_per_step, SLICE_C),
                                 lambda b, t, wl, na: (wl[b, t], 0))
        scratch = []
        kernel = functools.partial(_sell_batched_kernel, n_vertices,
                                   bottom_up)
        semantics = ("parallel", "arbitrary")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_batch, n_steps),
        in_specs=[cols_spec, rows_spec, whole(n_words), whole(n_words),
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
        name="bfs_sell_expand_batched",
    )(worklist, n_active, cols, slab_rows, frontier, visited, out_init,
      p_init)
    return out, parent


# ---------------------------------------------------------------------------
# SELL megakernel: the whole layer in ONE Pallas call (ISSUE 9).
#
# The active-step scheduling above rides scalar-prefetched BlockSpec
# index maps, which forces the slab plan onto the host side of the
# launch — the reason `SellFormat.supports_megakernel` stayed False
# through PR 6.  These kernels restructure the sweep around manual
# `make_async_copy` DMA exactly like `layer_fused.py`: the plan runs
# *inside* the kernel at step 0 (frontier x slab_rows membership,
# compacted with the same rank-scatter idiom — no host work-list), the
# SMEM work-list drives the cols DMA pipeline, and step n-1 inlines
# the restoration pass.  ``slab_rows`` stays fully VMEM-resident: the
# plan must read every slab's lane owners anyway, and at 128 int32 per
# slab it is W_QUANT x smaller than the cols stream it lets us skip.
# ---------------------------------------------------------------------------


def _plan_slabs_in_kernel(n_vertices: int, spp: int, n_steps: int,
                          words, slab_rows):
    """The in-kernel transcription of `formats.sell._plan_slab_steps`:
    from the (W,) planning bitmap (frontier, or ~visited bottom-up)
    and the resident (n_slabs, C) ``slab_rows``, build the compacted
    active slab-group work-list.  Same contract as
    `layer_fused._plan_in_kernel`: active prefix first, tail clamped
    to the last active group, plus the live count.  ``slab_rows`` must
    be pre-padded to an ``spp`` multiple (sentinel rows are never
    members, so padding slabs plan inactive — the zero-pad of the host
    planner)."""
    sw = jnp.clip(slab_rows >> WORD_SHIFT, 0, words.shape[0] - 1)
    sb = (slab_rows & WORD_MASK).astype(jnp.uint32)
    member = ((words[sw] >> sb) & jnp.uint32(1)) != 0
    act_slab = (member & (slab_rows < n_vertices)).any(axis=1)
    covered = act_slab.reshape(n_steps, spp).any(axis=1).astype(jnp.int32)
    n_active = covered.sum(dtype=jnp.int32)
    # rank-scatter compaction (jnp.nonzero is unavailable in-kernel)
    rank = jnp.cumsum(covered) - covered
    steps = jnp.arange(n_steps, dtype=jnp.int32)
    wl = jnp.zeros((n_steps,), jnp.int32) \
        .at[jnp.where(covered != 0, rank, n_steps)] \
        .set(steps, mode="drop")
    last = wl[jnp.clip(n_active - 1, 0, n_steps - 1)]
    wl = jnp.where(steps < n_active, wl, last)
    return wl, n_active


def _sell_layer_kernel(n_vertices: int, bottom_up: bool, spp: int,
                       depth: int, n_steps: int, cols_ref, rows_ref,
                       frontier_ref, vis_ref, p0_ref, out_ref, p_ref,
                       na_out_ref, wl_ref, na_ref, cols_buf, sems):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _plan():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.uint32)
        p_ref[...] = p0_ref[...]
        words = ~vis_ref[...] if bottom_up else frontier_ref[...]
        wl, na = _plan_slabs_in_kernel(n_vertices, spp, n_steps, words,
                                       rows_ref[...])
        wl_ref[...] = wl
        na_ref[0] = na
        na_out_ref[0] = na

    def work(cols_blk):
        @pl.when(t < na_ref[0])
        def _work():
            rows_blk = rows_ref[pl.ds(wl_ref[t] * spp, spp), :]
            out, p = _sell_tile(n_vertices, bottom_up, cols_blk,
                                rows_blk, frontier_ref[...],
                                vis_ref[...], out_ref[...], p_ref[...])
            out_ref[...] = out
            p_ref[...] = p

    _dma_pipeline(cols_ref, cols_buf, sems, lambda s: wl_ref[s], spp,
                  depth, n_steps, t, t == 0, work)

    @pl.when(t == n_steps - 1)
    def _restore():
        out, p = _restore_in_kernel(n_vertices, out_ref[...], p_ref[...])
        out_ref[...] = out
        p_ref[...] = p


def _sell_layer_batched_kernel(n_vertices: int, bottom_up: bool,
                               spp: int, depth: int, n_steps: int,
                               cols_ref, rows_ref, frontier_ref,
                               vis_ref, p0_ref, out_ref, p_ref,
                               na_out_ref, wl_ref, na_ref, cols_buf,
                               sems):
    """Batched variant: grid (roots, slice steps), root axis outer and
    sequential — each root re-plans into the shared SMEM scratch at
    its step 0, exactly the `layer_fused._layer_batched_kernel`
    shape."""
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _plan():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.uint32)
        p_ref[...] = p0_ref[...]
        words = ~vis_ref[0] if bottom_up else frontier_ref[0]
        wl, na = _plan_slabs_in_kernel(n_vertices, spp, n_steps, words,
                                       rows_ref[...])
        wl_ref[...] = wl
        na_ref[0] = na
        na_out_ref[0] = na

    def work(cols_blk):
        @pl.when(t < na_ref[0])
        def _work():
            rows_blk = rows_ref[pl.ds(wl_ref[t] * spp, spp), :]
            out, p = _sell_tile(n_vertices, bottom_up, cols_blk,
                                rows_blk, frontier_ref[0], vis_ref[0],
                                out_ref[0], p_ref[0])
            out_ref[...] = out[None]
            p_ref[...] = p[None]

    _dma_pipeline(cols_ref, cols_buf, sems, lambda s: wl_ref[s], spp,
                  depth, n_steps, t, t == 0, work)

    @pl.when(t == n_steps - 1)
    def _restore():
        out, p = _restore_in_kernel(n_vertices, out_ref[0], p_ref[0])
        out_ref[...] = out[None]
        p_ref[...] = p[None]


def megakernel_vmem_budget(n_words: int, v_pad: int, n_slabs: int,
                           slabs_per_step: int, prefetch_depth: int = 0,
                           n_steps: int = 1) -> int:
    """Bytes of VMEM the SELL megakernel pins: bitmaps x3 + P x2 + the
    fully resident ``slab_rows`` (x2 for the plan's membership working
    set) + the cols slab DMA buffers at the *clamped* pipeline
    depth + the SMEM work-list."""
    depth = min(max(int(prefetch_depth), 0), max(int(n_steps), 1))
    slab_cols = slabs_per_step * W_QUANT * SLICE_C * 4
    plan = 2 * 4 * n_slabs * SLICE_C + 4 * 3 * (n_steps + 1)
    return 4 * (3 * n_words + 2 * v_pad) \
        + (depth + 1) * slab_cols + plan


@functools.partial(jax.jit, static_argnames=("n_vertices",
                                             "slabs_per_step",
                                             "bottom_up",
                                             "prefetch_depth",
                                             "interpret"))
def sell_layer_fused(cols, slab_rows, frontier, visited, p_init, *,
                     n_vertices: int, slabs_per_step: int = 1,
                     bottom_up: bool = False, prefetch_depth: int = 0,
                     interpret: bool = True):
    """One SELL layer in ONE Pallas call: in-kernel slab plan + manual
    cols DMA + slab sweep + restoration.

    Same contract as `layer_fused.layer_fused`: returns the RESTORED
    ``(out, parent, n_active)`` — no host planning pass, no separate
    restore launch.  ``cols``/``slab_rows`` must be pre-padded to a
    ``slabs_per_step`` multiple (`ops._pad_slabs`).
    """
    n_slabs = cols.shape[0]
    assert n_slabs % slabs_per_step == 0, \
        "pad the slab count to the step size"
    n_steps = n_slabs // slabs_per_step
    n_words = visited.shape[0]
    v_pad = p_init.shape[0]
    depth = min(max(int(prefetch_depth), 0), n_steps)

    whole = lambda n: pl.BlockSpec((n,), lambda t: (0,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(n_steps,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
                  pl.BlockSpec((n_slabs, SLICE_C), lambda t: (0, 0)),
                  whole(n_words), whole(n_words), whole(v_pad)],
        out_specs=[whole(n_words), whole(v_pad), whole(1)],
        scratch_shapes=[pltpu.SMEM((n_steps,), jnp.int32),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((depth + 1, slabs_per_step, W_QUANT,
                                    SLICE_C), jnp.int32),
                        pltpu.SemaphoreType.DMA((depth + 1,))],
    )
    out, parent, n_active = pl.pallas_call(
        functools.partial(_sell_layer_kernel, n_vertices, bottom_up,
                          slabs_per_step, depth, n_steps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_words,), jnp.uint32),
                   jax.ShapeDtypeStruct((v_pad,), jnp.int32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        compiler_params=compiler_params(
            # SMEM work-list + accumulating outputs => sequential grid
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="bfs_sell_layer_fused",
    )(cols, slab_rows, frontier, visited, p_init)
    return out, parent, n_active


@functools.partial(jax.jit, static_argnames=("n_vertices",
                                             "slabs_per_step",
                                             "bottom_up",
                                             "prefetch_depth",
                                             "interpret"))
def sell_layer_fused_batched(cols, slab_rows, frontier, visited,
                             p_init, *, n_vertices: int,
                             slabs_per_step: int = 1,
                             bottom_up: bool = False,
                             prefetch_depth: int = 0,
                             interpret: bool = True):
    """Multi-root SELL megakernel: B independent layer sweeps in one
    launch, each root planning its own in-kernel work-list."""
    n_slabs = cols.shape[0]
    assert n_slabs % slabs_per_step == 0, \
        "pad the slab count to the step size"
    n_steps = n_slabs // slabs_per_step
    n_batch, n_words = visited.shape
    v_pad = p_init.shape[1]
    depth = min(max(int(prefetch_depth), 0), n_steps)

    whole = lambda n: pl.BlockSpec((1, n), lambda b, t: (b, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(n_batch, n_steps),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
                  pl.BlockSpec((n_slabs, SLICE_C), lambda b, t: (0, 0)),
                  whole(n_words), whole(n_words), whole(v_pad)],
        out_specs=[whole(n_words), whole(v_pad),
                   pl.BlockSpec((1,), lambda b, t: (b,))],
        scratch_shapes=[pltpu.SMEM((n_steps,), jnp.int32),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((depth + 1, slabs_per_step, W_QUANT,
                                    SLICE_C), jnp.int32),
                        pltpu.SemaphoreType.DMA((depth + 1,))],
    )
    out, parent, n_active = pl.pallas_call(
        functools.partial(_sell_layer_batched_kernel, n_vertices,
                          bottom_up, slabs_per_step, depth, n_steps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_batch, n_words), jnp.uint32),
                   jax.ShapeDtypeStruct((n_batch, v_pad), jnp.int32),
                   jax.ShapeDtypeStruct((n_batch,), jnp.int32)],
        compiler_params=compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="bfs_sell_layer_fused_batched",
    )(cols, slab_rows, frontier, visited, p_init)
    return out, parent, n_active


# ---------------------------------------------------------------------------
# Semiring relaxation over SELL slabs (ISSUE 10): the SpMV reading of
# SlimSell taken literally — the slab sweep IS a semiring
# matrix-vector product, and this kernel runs it over the (min, ⊗)
# pair of `algorithms/semiring.py` instead of the BFS bit test-and-set.
# Same two-phase shape as `gather_expand.gather_relax_batched`: grid
# (B, 2, steps), phase 0 folds candidates into the value row with a
# masked scatter-min (commutative — no §3.3.2 race, no restoration),
# phase 1 re-walks the same slabs and resolves the deterministic
# min-id parent among edges achieving the finalized optimum.
# ---------------------------------------------------------------------------


def _sell_relax_edges(n_vertices: int, unit: int, weighted: bool, cols,
                      rows, frontier, vals):
    """Per-slab edge enumeration for the semiring sweep: (src, nbr,
    mask, cand) with ``cand = vals[src] ⊗ w(src, nbr)``."""
    from repro.algorithms.semiring import edge_weight

    nbr = cols
    src = jnp.broadcast_to(rows[:, None, :], cols.shape)
    valid = (src < n_vertices) & (nbr < n_vertices)
    sw = jnp.clip(src >> WORD_SHIFT, 0, frontier.shape[0] - 1)
    sb = (src & WORD_MASK).astype(jnp.uint32)
    in_front = ((frontier[sw] >> sb) & jnp.uint32(1)) != 0
    mask = valid & in_front
    u_val = vals[jnp.clip(src, 0, vals.shape[0] - 1)]
    if weighted:
        cand = u_val + edge_weight(src, nbr)
    elif unit:
        cand = u_val + jnp.asarray(unit, vals.dtype)
    else:
        cand = u_val
    return src, nbr, mask, cand


def _sell_relax_batched_kernel(n_vertices: int, unit: int,
                               weighted: bool, wl_ref, na_ref, cols_ref,
                               rows_ref, frontier_ref, vals_ref,
                               out_ref, p_ref):
    b = pl.program_id(0)
    ph = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when((ph == 0) & (t == 0))
    def _init():
        out_ref[...] = vals_ref[...]
        p_ref[...] = jnp.full(p_ref.shape, P_UNSET, jnp.int32)

    @pl.when(t < na_ref[b])
    def _work():
        src, nbr, mask, cand = _sell_relax_edges(
            n_vertices, unit, weighted, cols_ref[...], rows_ref[...],
            frontier_ref[0], vals_ref[0])
        v_slots = p_ref.shape[1]

        @pl.when(ph == 0)
        def _vals():
            out_ref[...] = _relax_scatter_vals(
                v_slots, src, nbr, mask, cand, out_ref[0])[None]

        @pl.when(ph == 1)
        def _parents():
            p_ref[...] = _relax_scatter_parents(
                v_slots, src, nbr, mask, cand, vals_ref[0], out_ref[0],
                p_ref[0])[None]


@functools.partial(jax.jit, static_argnames=("n_vertices",
                                             "slabs_per_step", "unit",
                                             "weighted", "interpret"))
def sell_relax_batched(cols, slab_rows, worklist, n_active, frontier,
                       vals, *, n_vertices: int, slabs_per_step: int = 1,
                       unit: int = 0, weighted: bool = False,
                       interpret: bool = True):
    """Multi-root semiring SpMV sweep over the active slab groups.

    Same schedule contract as `sell_expand_batched` (per-root scalar-
    prefetched work-lists, clamped tails); same return contract as
    `gather_expand.gather_relax_batched`: ``(out_vals, p_layer)`` with
    ``p_layer == P_UNSET`` where no edge won — the driver merges under
    the improved mask.  No restoration (scatter-min commutes).
    """
    n_slabs = cols.shape[0]
    assert n_slabs % slabs_per_step == 0, \
        "pad the slab count to the step size"
    n_steps = n_slabs // slabs_per_step
    n_batch, n_words = frontier.shape
    assert worklist.shape == (n_batch, n_steps)
    v_pad = vals.shape[1]

    whole = lambda n: pl.BlockSpec((1, n),
                                   lambda b, ph, t, wl, na: (b, 0))
    cols_spec = pl.BlockSpec((slabs_per_step, W_QUANT, SLICE_C),
                             lambda b, ph, t, wl, na: (wl[b, t], 0, 0))
    rows_spec = pl.BlockSpec((slabs_per_step, SLICE_C),
                             lambda b, ph, t, wl, na: (wl[b, t], 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # phase-major sequential: phase 1 reads finalized values
        grid=(n_batch, 2, n_steps),
        in_specs=[cols_spec, rows_spec, whole(n_words), whole(v_pad)],
        out_specs=[whole(v_pad), whole(v_pad)],
    )
    out_vals, p_layer = pl.pallas_call(
        functools.partial(_sell_relax_batched_kernel, n_vertices, unit,
                          weighted),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_batch, v_pad), vals.dtype),
                   jax.ShapeDtypeStruct((n_batch, v_pad), jnp.int32)],
        compiler_params=compiler_params(
            dimension_semantics=("arbitrary", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
        name="bfs_sell_relax_batched",
    )(worklist, n_active, cols, slab_rows, frontier, vals)
    return out_vals, p_layer
