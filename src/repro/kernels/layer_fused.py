"""Pallas TPU kernel: the whole-layer BFS megakernel (ISSUE 6).

One Pallas call per layer.  The three launches the fused pipeline
issues every layer — packed frontier compaction, active-tile planning
(a jnp pass feeding scalar prefetch), and the gather-expand sweep,
plus a fourth for restoration — collapse into a single persistent
kernel whose sequential grid walks the rows-blocks of the CSR:

* **grid step 0 — in-kernel plan + compact.**  The frontier bitmap
  (or its complement, bottom-up) unpacks in-register to a dense
  activity vector; the adjacency ranges of active vertices range-mark
  the rows-blocks with the same +1/-1 difference scatter + prefix sum
  as `engine._mark_blocks`, and a cumsum-rank masked scatter (the
  `compact.py` rank-and-scatter, applied to block marks) compacts the
  covered blocks into a work-list that never leaves the chip: it is
  written to SMEM scratch and read back like a scalar-prefetch
  operand.  No ``jnp.nonzero``, no HBM round trip — the §4 "queue
  generation" runs against block marks inside the sweep kernel
  itself.
* **grid steps t < n_active — gather-expand.**  Because the work-list
  is computed *inside* the kernel, a BlockSpec index map (which binds
  before launch) cannot drive the rows DMA; the kernel instead keeps
  ``rows`` in HBM (ANY memory space) and issues its own
  ``make_async_copy`` per active block through the shared
  `gather_expand._dma_pipeline` — ``prefetch_depth`` tile DMAs in
  flight ahead of the compute tile (depth 0 degrades to a synchronous
  start/wait copy).  The compute body is `_gather_tile` verbatim, so
  the racy expansion semantics (and therefore the bit-exact results)
  are shared with the unfused pipeline.
* **final grid step — in-kernel restoration.**  The §3.3.2 repair of
  racy bitmap drops (negative P marks -> +|V| restore + repacked
  delta OR'd into the output bitmap) runs over the VMEM-resident P
  before the outputs ship, eliminating the separate restoration
  launch.  Because every true discovery carries a negative P mark, the
  restored output bitmap equals the unfused path's ``out | delta``
  bit for bit.

The work-list clamp contract is `engine.compact_worklist`'s: entries
past ``n_active`` repeat the last active block (unchanged DMA source
=> Mosaic elides the copy; a ``pl.when`` guard skips the compute).
The kernel also emits ``n_active`` as a (1,) output so the engine's
bytes-accounting counters stay exact without a second planning pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitmap import BITS_PER_WORD, word_bits
from repro.kernels.gather_expand import (DEFAULT_TILE, _dma_pipeline,
                                         _gather_tile)
from repro.kernels import compiler_params


def _plan_in_kernel(n_vertices: int, tile: int, n_blocks: int,
                    bottom_up: bool, words, colstarts):
    """The in-kernel transcription of `engine.plan_active_tiles`'s
    dense arm: packed activity words -> (worklist, n_active), all in
    registers/VMEM.  Scatter-based (difference marks + cumsum ranks);
    no ``jnp.nonzero`` (which has no Mosaic lowering)."""
    if bottom_up:
        words = ~words
    dense = word_bits(words).reshape(-1)[:n_vertices] != 0
    start = colstarts[:-1]
    end = colstarts[1:]
    has = dense & (end > start)
    blk_lo = start // tile
    blk_hi = (end - 1) // tile
    drop = n_blocks + 1
    diff = jnp.zeros((n_blocks + 1,), jnp.int32)
    diff = diff.at[jnp.where(has, blk_lo, drop)].add(1, mode="drop")
    diff = diff.at[jnp.where(has, blk_hi + 1, drop)].add(-1, mode="drop")
    covered = (jnp.cumsum(diff)[:n_blocks] > 0).astype(jnp.int32)
    n_active = covered.sum(dtype=jnp.int32)
    # rank-and-scatter the covered block ids (compact.py idiom on
    # block marks), then clamp the tail to the last active block
    rank = jnp.cumsum(covered) - covered
    idx = jnp.where(covered != 0, rank, n_blocks)
    blocks = jnp.arange(n_blocks, dtype=jnp.int32)
    wl = jnp.zeros((n_blocks,), jnp.int32).at[idx].set(blocks,
                                                       mode="drop")
    last = wl[jnp.clip(n_active - 1, 0, n_blocks - 1)]
    wl = jnp.where(blocks < n_active, wl, last)
    return wl, n_active


def _restore_in_kernel(n_vertices: int, out, p):
    """The in-kernel transcription of `restoration._restoration_kernel`
    over the whole VMEM-resident P: negative marks -> restored P and
    the repaired output bitmap."""
    marked = p < 0
    p_fixed = jnp.where(marked, p + n_vertices, p)
    bits = marked.reshape(-1, BITS_PER_WORD).astype(jnp.uint32)
    weights = jnp.uint32(1) << jnp.arange(BITS_PER_WORD,
                                          dtype=jnp.uint32)
    delta = (bits * weights).sum(axis=1, dtype=jnp.uint32)
    return out | delta, p_fixed


def _layer_kernel(n_vertices: int, tile: int, n_cs: int,
                  bottom_up: bool, depth: int, n_blocks: int,
                  rows_ref, cs_ref, frontier_ref, vis_ref, p0_ref,
                  out_ref, p_ref, na_out_ref, wl_ref, na_ref, rows_buf,
                  sems):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _plan():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.uint32)
        p_ref[...] = p0_ref[...]
        words = vis_ref[...] if bottom_up else frontier_ref[...]
        wl, n_active = _plan_in_kernel(n_vertices, tile, n_blocks,
                                       bottom_up, words, cs_ref[...])
        wl_ref[...] = wl
        na_ref[0] = n_active
        na_out_ref[0] = n_active

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

    @pl.when(t == n_blocks - 1)
    def _restore():
        out, p = _restore_in_kernel(n_vertices, out_ref[...], p_ref[...])
        out_ref[...] = out
        p_ref[...] = p


def _layer_batched_kernel(n_vertices: int, tile: int, n_cs: int,
                          bottom_up: bool, depth: int, n_blocks: int,
                          rows_ref, cs_ref, frontier_ref, vis_ref,
                          p0_ref, out_ref, p_ref, na_out_ref, wl_ref,
                          na_ref, rows_buf, sems):
    """Batched variant: grid (roots, blocks), both sequential — the
    SMEM work-list scratch is re-planned at each root's first step
    and the DMA pipeline re-warms at root boundaries (exactly the
    batched-DMA gather contract)."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _plan():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.uint32)
        p_ref[...] = p0_ref[...]
        words = vis_ref[0] if bottom_up else frontier_ref[0]
        wl, n_active = _plan_in_kernel(n_vertices, tile, n_blocks,
                                       bottom_up, words, cs_ref[...])
        wl_ref[...] = wl
        na_ref[0] = n_active
        na_out_ref[0] = n_active

    def work(rows_blk):
        @pl.when(t < na_ref[0])
        def _work():
            out, p = _gather_tile(n_vertices, tile, n_cs, bottom_up,
                                  wl_ref[t], rows_blk, cs_ref[...],
                                  frontier_ref[0], vis_ref[0],
                                  out_ref[0], p_ref[0])
            out_ref[...] = out[None]
            p_ref[...] = p[None]

    _dma_pipeline(rows_ref, rows_buf, sems, lambda s: wl_ref[s], tile,
                  depth, n_blocks, t, t == 0, work)

    @pl.when(t == n_blocks - 1)
    def _restore():
        out, p = _restore_in_kernel(n_vertices, out_ref[0], p_ref[0])
        out_ref[...] = out[None]
        p_ref[...] = p[None]


def vmem_budget(n_words: int, v_pad: int, n_cs: int, tile: int,
                prefetch_depth: int = 0, n_blocks: int = 1) -> int:
    """Bytes of VMEM the megakernel pins: bitmaps x3 + P x2 +
    colstarts + the rows DMA buffers, PLUS the planning working set
    (the dense activity vector and the block-mark vectors) that the
    unfused pipeline keeps outside the kernel.

    The buffer count charges the *resolved* pipeline depth — the
    wrappers clamp ``prefetch_depth`` to ``n_blocks``, so the budget
    must too, or a deep affinity-resolved prefetch on a small graph
    double-counts DMA buffers the kernel never allocates (ISSUE 9
    satellite)."""
    n_buf = min(max(int(prefetch_depth), 0),
                max(int(n_blocks), 1)) + 1
    plan = 4 * (v_pad + 3 * (n_blocks + 1))
    return (4 * (3 * n_words + 2 * v_pad + n_cs) + n_buf * 4 * tile
            + plan)


@functools.partial(jax.jit, static_argnames=("n_vertices", "tile",
                                             "bottom_up",
                                             "prefetch_depth",
                                             "interpret"))
def layer_fused(rows, colstarts, frontier, visited, p_init, *,
                n_vertices: int, tile: int = DEFAULT_TILE,
                bottom_up: bool = False, prefetch_depth: int = 0,
                interpret: bool = True):
    """One BFS layer in ONE Pallas call: plan + compact + gather-expand
    + restoration (see the module docstring).

    Args:
      rows: (E_tiles,) int32 CSR adjacency, sentinel-padded to a tile
        multiple (pad once at build).  Stays in HBM; the kernel DMAs
        active blocks itself.
      colstarts: (V + 1,) int32, VMEM-resident.
      frontier, visited: (W,) uint32 bitmaps.
      p_init: (V_pad,) int32 predecessor array.
      bottom_up: plan from the unvisited complement and swap the
        gate/discover roles (the hybrid direction).
      prefetch_depth: tile DMAs kept in flight ahead of the compute
        tile (0 = synchronous copy per block).
    Returns:
      (out, parent, n_active): the RESTORED layer outputs — ``out``
      already includes the repair delta, ``parent`` is non-negative —
      plus the (1,) count of active blocks the in-kernel plan found.
    """
    n_slots = rows.shape[0]
    assert n_slots % tile == 0, "pad rows to the tile size at build"
    n_blocks = n_slots // tile
    n_cs = colstarts.shape[0]
    n_words = visited.shape[0]
    v_pad = p_init.shape[0]
    depth = min(max(int(prefetch_depth), 0), n_blocks)

    whole = lambda n: pl.BlockSpec((n,), lambda t: (0,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
                  whole(n_cs), whole(n_words), whole(n_words),
                  whole(v_pad)],
        out_specs=[whole(n_words), whole(v_pad), whole(1)],
        scratch_shapes=[pltpu.SMEM((n_blocks,), jnp.int32),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((depth + 1, tile), jnp.int32),
                        pltpu.SemaphoreType.DMA((depth + 1,))],
    )
    out, parent, n_active = pl.pallas_call(
        functools.partial(_layer_kernel, n_vertices, tile, n_cs,
                          bottom_up, depth, n_blocks),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_words,), jnp.uint32),
                   jax.ShapeDtypeStruct((v_pad,), jnp.int32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        compiler_params=compiler_params(
            # scratch work-list + accumulating outputs => sequential
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="bfs_layer_fused",
    )(rows, colstarts, frontier, visited, p_init)
    return out, parent, n_active


@functools.partial(jax.jit, static_argnames=("n_vertices", "tile",
                                             "bottom_up",
                                             "prefetch_depth",
                                             "interpret"))
def layer_fused_batched(rows, colstarts, frontier, visited, p_init, *,
                        n_vertices: int, tile: int = DEFAULT_TILE,
                        bottom_up: bool = False,
                        prefetch_depth: int = 0,
                        interpret: bool = True):
    """Multi-root megakernel: one launch, B whole layers.

    The adjacency carries no root axis (shared layout); bitmaps/P are
    (B, W) / (B, V_pad).  Grid is (B, n_blocks), fully sequential —
    each root re-plans its own work-list into the SMEM scratch at its
    first step.  Returns (out (B, W), parent (B, V_pad), n_active
    (B,)).
    """
    n_slots = rows.shape[0]
    assert n_slots % tile == 0, "pad rows to the tile size at build"
    n_blocks = n_slots // tile
    n_batch = visited.shape[0]
    n_cs = colstarts.shape[0]
    n_words = visited.shape[1]
    v_pad = p_init.shape[1]
    depth = min(max(int(prefetch_depth), 0), n_blocks)

    flat = lambda n: pl.BlockSpec((n,), lambda b, t: (0,))
    whole = lambda n: pl.BlockSpec((1, n), lambda b, t: (b, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(n_batch, n_blocks),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
                  flat(n_cs), whole(n_words), whole(n_words),
                  whole(v_pad)],
        out_specs=[whole(n_words), whole(v_pad),
                   pl.BlockSpec((1,), lambda b, t: (b,))],
        scratch_shapes=[pltpu.SMEM((n_blocks,), jnp.int32),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((depth + 1, tile), jnp.int32),
                        pltpu.SemaphoreType.DMA((depth + 1,))],
    )
    out, parent, n_active = pl.pallas_call(
        functools.partial(_layer_batched_kernel, n_vertices, tile,
                          n_cs, bottom_up, depth, n_blocks),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_batch, n_words), jnp.uint32),
                   jax.ShapeDtypeStruct((n_batch, v_pad), jnp.int32),
                   jax.ShapeDtypeStruct((n_batch,), jnp.int32)],
        compiler_params=compiler_params(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="bfs_layer_fused_batched",
    )(rows, colstarts, frontier, visited, p_init)
    return out, parent, n_active
