"""Pallas TPU kernel: the whole-traversal persistent BFS kernel (ISSUE 9).

ONE Pallas call per *traversal*.  The PR-6 megakernel collapsed a
layer's launches into one call but left the layer loop in a
``lax.while_loop`` that re-dispatches per layer — small-diameter
graphs pay per-launch overhead L times and the direction decision
bounces through XLA carry state.  This kernel moves the layer loop
*inside* the kernel and keeps the whole search state resident:

* **grid = (1,)** — a single persistent grid step.  Every loop (layer
  x root x rows-block) is a ``lax.fori_loop`` inside the kernel body,
  so interpret mode traces each body once instead of unrolling a
  layers x blocks grid.
* **state lives in the output refs.**  frontier/visited/P copy from
  the inputs once, then every layer mutates them in place — VMEM
  residency across layers is the point: no HBM round trip of the
  bitmaps between layers, no while_loop carry.
* **direction/termination on in-kernel counters.**  The Table 1
  workload counters (frontier popcount, masked degree sums) are
  computed from the VMEM-resident bitmaps each layer and fed to the
  *engine's own policy object* (closed over statically — policies are
  pure jnp, so `policy.decide` traces straight into the kernel).  An
  empty frontier drops the ``live`` flag and the remaining layer
  iterations become no-ops — the in-kernel transcription of the
  engine's while condition.
* **per-layer sweep = the megakernel body.**  Each live layer plans
  its work-list with `layer_fused._plan_in_kernel`, streams the
  active rows-blocks through a manual `make_async_copy` pipeline
  (``prefetch_depth`` tiles in flight), expands with the
  direction/mode-blended `_gather_tile` body and repairs racy drops
  with `layer_fused._restore_in_kernel` before the next layer reads
  the state.

Mode parity with the per-layer engine is exact by construction: SIMD
and bottom-up layers use the accumulating ``vis | out`` undiscovered
test (`frontier_expand._expand_tile` — first tile wins), while
MODE_SCALAR layers test against the pre-layer ``visited`` only, so an
ascending-block sweep reproduces the jnp `expand_candidates` scatter's
global last-write-wins bit for bit.  ``LayerStats.launches`` therefore
charges 1 on layer 0 and 0 elsewhere — one launch per traversal, the
number CI gate 5 pins.

The SELL-C-σ variant (`sell_traversal_fused_batched`) swaps the
rows-block gather for the slab sweep of `sell_expand._sell_tile`,
planned by the in-kernel slab membership pass
(`sell_expand._plan_slabs_in_kernel`) — ``slab_rows`` stays fully
VMEM-resident (the plan reads every slab's lane owners), only the
``cols`` slabs stream through the DMA pipeline.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitmap import WORD_MASK, WORD_SHIFT, word_bits
from repro.kernels.gather_expand import DEFAULT_TILE, _owner_search
from repro.kernels.layer_fused import _plan_in_kernel, _restore_in_kernel
from repro.kernels import compiler_params
from repro.kernels.sell_expand import (SLICE_C, W_QUANT,
                                       _plan_slabs_in_kernel)

# Engine mode constants, restated locally: this module sits below
# core/engine.py in the import graph (ops.py wraps these kernels and
# the engine imports ops), so importing the engine here would be a
# cycle.  tests/test_persistent.py pins these against the engine's.
MODE_SCALAR = 0
MODE_SIMD = 1
MODE_BOTTOMUP = 2

_N_ST = 8           # stats buffer columns (engine._N_ST)


class _Workload(NamedTuple):
    """Duck-typed stand-in for `engine.Workload` (same fields, same
    order).  Policies only read attributes, so the engine's frozen
    policy objects decide *inside* the kernel trace without this
    module importing the engine."""
    layer: jax.Array
    frontier_vertices: jax.Array
    frontier_edges: jax.Array
    unvisited_vertices: jax.Array
    unvisited_edges: jax.Array
    n_vertices: int
    bottom_up: jax.Array
    n_roots: int = 1


def _layer_counters(n_vertices: int, words, deg):
    """Per-root Table 1 counters from a packed (B, W) bitmap: set-bit
    counts and masked degree sums — the in-kernel transcription of
    `engine.row_popcounts` + `bitmap.masked_degree_sum`."""
    count_b = jax.lax.population_count(words).astype(jnp.int32) \
        .sum(axis=1)
    n_batch = words.shape[0]
    dense = word_bits(words).reshape(n_batch, -1)[:, :n_vertices]
    edges_b = (dense * deg).sum(axis=1, dtype=jnp.int32)
    return count_b, edges_b


def _decide(policy, layer, f_count_b, f_edges_b, vis, deg,
            n_vertices: int, n_batch: int, bottom_up):
    """The engine's measure+decide phase on in-kernel counters: batch
    sums aggregate in float32 exactly like `engine._traverse_impl`
    (per-root counts are int32-safe; a batch sum may not be)."""
    if policy.needs_unvisited:
        u_words = ~vis
        u_count_b, u_edges_b = _layer_counters(n_vertices, u_words, deg)
        u_count = u_count_b.sum().astype(jnp.float32)
        u_edges = u_edges_b.astype(jnp.float32).sum()
    else:
        u_count = u_edges = jnp.float32(0)
    w = _Workload(layer, f_count_b.astype(jnp.float32).sum(),
                  f_edges_b.astype(jnp.float32).sum(), u_count, u_edges,
                  n_vertices, bottom_up, n_roots=n_batch)
    return policy.decide(w)


def _gather_tile_dyn(n_vertices: int, tile: int, n_cs: int, is_bu,
                     is_scalar, blk, rows_blk, colstarts, frontier, vis,
                     out, p):
    """`gather_expand._gather_tile` with the direction and the
    mode-dependent undiscovered test as *traced* selects — the layer
    loop decides both at run time, so the per-layer kernels' static
    role swap becomes a `jnp.where` blend here.

    The mode select mirrors the megakernel pipeline's step table:
    SIMD/bottom-up layers share `_expand_tile`'s accumulating
    ``vis | out`` test (first tile wins), while MODE_SCALAR layers
    test against the *pre-layer* visited only — a vertex discovered by
    an earlier tile can be re-discovered and its P overwritten, so the
    ascending-block sweep reproduces the jnp `expand_candidates`
    scatter's global last-write-wins exactly."""
    e_idx = blk * tile + jnp.arange(tile, dtype=jnp.int32)
    u = _owner_search(colstarts, e_idx, n_cs)
    v = rows_blk
    valid = (u < n_vertices) & (v < n_vertices)
    nbr = jnp.where(is_bu, v, u)
    cand = jnp.where(is_bu, u, v)

    word = cand >> WORD_SHIFT
    bit = (cand & WORD_MASK).astype(jnp.uint32)
    bits = jnp.uint32(1) << bit
    w_clip = jnp.clip(word, 0, out.shape[0] - 1)
    vis_words = vis[w_clip]
    out_words = out[w_clip]
    undis = jnp.where(is_scalar, (vis_words & bits) == 0,
                      ((vis_words | out_words) & bits) == 0)
    nw = jnp.clip(nbr >> WORD_SHIFT, 0, frontier.shape[0] - 1)
    nb = (nbr & WORD_MASK).astype(jnp.uint32)
    in_front = (frontier[nw] & (jnp.uint32(1) << nb)) != 0
    mask = valid & undis & in_front

    p_idx = jnp.where(mask, cand, p.shape[0])
    new_p = p.at[p_idx].set(nbr - n_vertices, mode="drop")
    new_words = out_words | bits
    w_idx = jnp.where(mask, word, out.shape[0])
    new_out = out.at[w_idx].set(new_words, mode="drop")
    return new_out, new_p


def _sell_tile_dyn(n_vertices: int, is_bu, cols, rows, frontier, vis,
                   out, p):
    """`sell_expand._sell_tile` with the gate/discover role swap as a
    traced select (the persistent layer loop decides direction at run
    time).  SELL maps every engine mode onto this one sweep
    (``algorithm="simd"`` — the format's step table), so there is no
    scalar-mode blend here: the accumulating ``vis | out`` test IS the
    per-layer kernel's semantics for all modes."""
    nbr = cols
    src = jnp.broadcast_to(rows[:, None, :], cols.shape)
    gate = jnp.where(is_bu, nbr, src)
    disc = jnp.where(is_bu, src, nbr)

    sw = jnp.clip(gate >> WORD_SHIFT, 0, frontier.shape[0] - 1)
    sb = (gate & WORD_MASK).astype(jnp.uint32)
    in_front = (frontier[sw] >> sb) & jnp.uint32(1) != 0

    word = disc >> WORD_SHIFT
    bit = (disc & WORD_MASK).astype(jnp.uint32)
    bits = jnp.uint32(1) << bit
    w_clip = jnp.clip(word, 0, out.shape[0] - 1)
    out_words = out[w_clip]
    undiscovered = ((vis[w_clip] | out_words) & bits) == 0
    mask = (in_front & undiscovered
            & (nbr < n_vertices) & (src < n_vertices))

    p_idx = jnp.where(mask, disc, p.shape[0])
    new_p = p.at[p_idx].set(gate - n_vertices, mode="drop")
    new_words = out_words | bits
    w_idx = jnp.where(mask, word, out.shape[0])
    new_out = out.at[w_idx].set(new_words, mode="drop")
    return new_out, new_p


def _persistent_layer_loop(policy, n_vertices: int, n_batch: int,
                           max_layers: int, deg, f_ref, vis_ref, p_ref,
                           depths_ref, layers_ref, stats_ref,
                           sweep_root):
    """The layer x root scaffold shared by the CSR and SELL persistent
    kernels: init outputs from inputs is done by the caller; this runs
    the in-kernel measure -> decide -> sweep -> restore -> stats loop.

    ``sweep_root(is_bu, is_scalar, live, f_b, vis_b, p_b)`` returns the
    un-restored ``(out_b, p_b, n_active)`` for one root's layer sweep.
    """
    def layer_body(l, bottom_up):
        frontier = f_ref[...]
        vis = vis_ref[...]
        f_count_b, f_edges_b = _layer_counters(n_vertices, frontier, deg)
        live = f_count_b.sum() > 0
        mode, new_bu = _decide(policy, l, f_count_b, f_edges_b, vis,
                               deg, n_vertices, n_batch, bottom_up)
        is_bu = mode == jnp.int32(MODE_BOTTOMUP)
        is_scalar = mode == jnp.int32(MODE_SCALAR)

        def root_body(b, na_sum):
            f_b = f_ref[pl.ds(b, 1), :][0]
            vis_b = vis_ref[pl.ds(b, 1), :][0]
            p_b = p_ref[pl.ds(b, 1), :][0]
            out_b, p_new, na = sweep_root(is_bu, is_scalar, live, f_b,
                                          vis_b, p_b)
            out_b, p_new = _restore_in_kernel(n_vertices, out_b, p_new)
            # in-place per-root update is safe: later roots in this
            # layer read only their own rows, and the batch counters
            # above were read before the root loop started
            f_ref[pl.ds(b, 1), :] = out_b[None]
            vis_ref[pl.ds(b, 1), :] = (vis_b | out_b)[None]
            p_ref[pl.ds(b, 1), :] = p_new[None]
            return na_sum + na

        na_sum = jax.lax.fori_loop(0, n_batch, root_body, jnp.int32(0))

        @pl.when(live)
        def _stats():
            discovered = jax.lax.population_count(f_ref[...]) \
                .astype(jnp.int32).sum()
            # launches: ONE Pallas call per traversal, charged to the
            # first layer's row (the stats contract stays per-layer)
            launches = jnp.where(l == 0, jnp.int32(1), jnp.int32(0))
            row = jnp.stack([f_count_b.sum(), f_edges_b.sum(),
                             discovered, mode, jnp.int32(1), na_sum,
                             jnp.int32(0), launches])
            stats_ref[pl.ds(l, 1), :] = row[None]
            depths_ref[...] = depths_ref[...] \
                + (f_count_b > 0).astype(jnp.int32)
            layers_ref[...] = layers_ref[...] + 1

        return jnp.where(live, new_bu, bottom_up)

    jax.lax.fori_loop(0, max_layers, layer_body, jnp.asarray(False))


def _init_state(f0_ref, vis0_ref, p0_ref, f_ref, vis_ref, p_ref,
                depths_ref, layers_ref, stats_ref):
    f_ref[...] = f0_ref[...]
    vis_ref[...] = vis0_ref[...]
    p_ref[...] = p0_ref[...]
    depths_ref[...] = jnp.zeros(depths_ref.shape, jnp.int32)
    layers_ref[...] = jnp.zeros(layers_ref.shape, jnp.int32)
    stats_ref[...] = jnp.zeros(stats_ref.shape, jnp.int32)


def _traversal_kernel(n_vertices: int, tile: int, n_cs: int, depth: int,
                      n_blocks: int, max_layers: int, n_batch: int,
                      policy, rows_ref, cs_ref, f0_ref, vis0_ref,
                      p0_ref, f_ref, vis_ref, p_ref, depths_ref,
                      layers_ref, stats_ref, rows_buf, sems):
    _init_state(f0_ref, vis0_ref, p0_ref, f_ref, vis_ref, p_ref,
                depths_ref, layers_ref, stats_ref)
    cs = cs_ref[...]
    deg = cs[1:] - cs[:-1]
    n_buf = depth + 1

    def sweep_root(is_bu, is_scalar, live, f_b, vis_b, p_b):
        words_b = jnp.where(is_bu, ~vis_b, f_b)
        wl, na = _plan_in_kernel(n_vertices, tile, n_blocks, False,
                                 words_b, cs)
        na = jnp.where(live, na, jnp.int32(0))

        def dma(step):
            slot = jax.lax.rem(step, n_buf)
            return pltpu.make_async_copy(
                rows_ref.at[pl.ds(wl[step] * tile, tile)],
                rows_buf.at[slot], sems.at[slot])

        # the pipeline re-warms per root sweep (the clamped work-list
        # tail makes every source index valid, so warmup DMAs are
        # always legal — `gather_expand._dma_pipeline`'s contract)
        for k in range(min(depth, n_blocks)):
            dma(jnp.int32(k)).start()

        def blk_body(t, op):
            out_b, pp = op

            @pl.when(t + depth < n_blocks)
            def _ahead():
                dma(t + depth).start()

            dma(t).wait()
            rows_blk = rows_buf[jax.lax.rem(t, n_buf)]
            new_out, new_p = _gather_tile_dyn(
                n_vertices, tile, n_cs, is_bu, is_scalar, wl[t],
                rows_blk, cs, f_b, vis_b, out_b, pp)
            # inactive tiles: the DMA ran (balanced start/wait sets)
            # but the compute result is discarded — the value-carry
            # analogue of the grid kernels' `pl.when` guard
            act = t < na
            return (jnp.where(act, new_out, out_b),
                    jnp.where(act, new_p, pp))

        out_b, p_b = jax.lax.fori_loop(
            0, n_blocks, blk_body, (jnp.zeros_like(f_b), p_b))
        return out_b, p_b, na

    _persistent_layer_loop(policy, n_vertices, n_batch, max_layers,
                           deg, f_ref, vis_ref, p_ref, depths_ref,
                           layers_ref, stats_ref, sweep_root)


def _sell_traversal_kernel(n_vertices: int, spp: int, depth: int,
                           n_steps: int, max_layers: int, n_batch: int,
                           policy, cols_ref, rows_ref, deg_ref, f0_ref,
                           vis0_ref, p0_ref, f_ref, vis_ref, p_ref,
                           depths_ref, layers_ref, stats_ref, cols_buf,
                           sems):
    _init_state(f0_ref, vis0_ref, p0_ref, f_ref, vis_ref, p_ref,
                depths_ref, layers_ref, stats_ref)
    slab_rows = rows_ref[...]        # VMEM-resident: the plan reads all
    deg = deg_ref[...]
    n_buf = depth + 1

    def sweep_root(is_bu, is_scalar, live, f_b, vis_b, p_b):
        del is_scalar    # SELL maps every mode onto the one slab sweep
        words_b = jnp.where(is_bu, ~vis_b, f_b)
        wl, na = _plan_slabs_in_kernel(n_vertices, spp, n_steps,
                                       words_b, slab_rows)
        na = jnp.where(live, na, jnp.int32(0))

        def dma(step):
            slot = jax.lax.rem(step, n_buf)
            return pltpu.make_async_copy(
                cols_ref.at[pl.ds(wl[step] * spp, spp)],
                cols_buf.at[slot], sems.at[slot])

        for k in range(min(depth, n_steps)):
            dma(jnp.int32(k)).start()

        def blk_body(t, op):
            out_b, pp = op

            @pl.when(t + depth < n_steps)
            def _ahead():
                dma(t + depth).start()

            dma(t).wait()
            cols_blk = cols_buf[jax.lax.rem(t, n_buf)]
            rows_blk = rows_ref[pl.ds(wl[t] * spp, spp), :]
            new_out, new_p = _sell_tile_dyn(
                n_vertices, is_bu, cols_blk, rows_blk, f_b, vis_b,
                out_b, pp)
            act = t < na
            return (jnp.where(act, new_out, out_b),
                    jnp.where(act, new_p, pp))

        out_b, p_b = jax.lax.fori_loop(
            0, n_steps, blk_body, (jnp.zeros_like(f_b), p_b))
        return out_b, p_b, na

    _persistent_layer_loop(policy, n_vertices, n_batch, max_layers,
                           deg, f_ref, vis_ref, p_ref, depths_ref,
                           layers_ref, stats_ref, sweep_root)


def vmem_budget(n_words: int, v_pad: int, n_cs: int, tile: int,
                n_batch: int = 1, max_layers: int = 64,
                prefetch_depth: int = 0, n_blocks: int = 1) -> int:
    """Bytes of VMEM the CSR persistent kernel pins: the whole batch's
    state x2 (input copies + resident outputs) + colstarts + the
    planning working set + the rows DMA buffers + the stats buffer.
    The DMA depth is clamped to ``n_blocks`` exactly as the kernel
    clamps it (the resolved-spec budget rule of ISSUE 9)."""
    depth = min(max(int(prefetch_depth), 0), max(int(n_blocks), 1))
    state = 2 * 4 * n_batch * (2 * n_words + v_pad)
    plan = 4 * (v_pad + 3 * (n_blocks + 1))
    stats = 4 * (_N_ST * max_layers + n_batch + 1)
    return state + 4 * n_cs + (depth + 1) * 4 * tile + plan + stats


def sell_vmem_budget(n_words: int, v_pad: int, n_slabs: int, spp: int,
                     n_batch: int = 1, max_layers: int = 64,
                     prefetch_depth: int = 0, n_steps: int = 1) -> int:
    """Bytes of VMEM the SELL persistent kernel pins: batch state x2 +
    the fully resident ``slab_rows`` (the in-kernel plan reads every
    slab's lane owners, charged x2 for the membership working set) +
    degrees + the cols slab DMA buffers + the stats buffer."""
    depth = min(max(int(prefetch_depth), 0), max(int(n_steps), 1))
    state = 2 * 4 * n_batch * (2 * n_words + v_pad)
    slab_cols = spp * W_QUANT * SLICE_C * 4
    plan = 2 * 4 * n_slabs * SLICE_C + 4 * 3 * (n_steps + 1)
    stats = 4 * (_N_ST * max_layers + n_batch + 1)
    return state + 4 * v_pad + plan + (depth + 1) * slab_cols + stats


@functools.partial(jax.jit, static_argnames=("n_vertices", "tile",
                                             "policy", "max_layers",
                                             "prefetch_depth",
                                             "interpret"))
def traversal_fused_batched(rows, colstarts, frontier, visited, p_init,
                            *, n_vertices: int, tile: int = DEFAULT_TILE,
                            policy, max_layers: int = 64,
                            prefetch_depth: int = 0,
                            interpret: bool = True):
    """The whole multi-root BFS traversal in ONE Pallas call.

    Args:
      rows: (E_tiles,) int32 CSR adjacency, sentinel-padded to a tile
        multiple (pad once at build).  Stays in HBM; active blocks are
        DMA'd per layer.
      colstarts: (V + 1,) int32, VMEM-resident for the whole search.
      frontier, visited: (B, W) uint32 initial bitmaps (root states).
      p_init: (B, V_pad) int32 predecessor arrays.
      policy: a frozen engine DirectionPolicy — closed over statically;
        `policy.decide` runs on in-kernel counters every layer.
      max_layers: the in-kernel layer cap (the engine's while bound).
    Returns:
      (frontier, visited, parent, depths (B,), layers (1,), stats
      (max_layers, 8)) — the engine's whole-traversal contract, with
      restoration applied every layer and the stats launch column
      charging 1 to layer 0 (one launch per traversal).
    """
    n_slots = rows.shape[0]
    assert n_slots % tile == 0, "pad rows to the tile size at build"
    n_blocks = n_slots // tile
    n_batch, n_words = visited.shape
    n_cs = colstarts.shape[0]
    v_pad = p_init.shape[1]
    depth = min(max(int(prefetch_depth), 0), n_blocks)

    whole = lambda *s: pl.BlockSpec(s, lambda t: (0,) * len(s))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
                  whole(n_cs), whole(n_batch, n_words),
                  whole(n_batch, n_words), whole(n_batch, v_pad)],
        out_specs=[whole(n_batch, n_words), whole(n_batch, n_words),
                   whole(n_batch, v_pad), whole(n_batch), whole(1),
                   whole(max_layers, _N_ST)],
        scratch_shapes=[pltpu.VMEM((depth + 1, tile), jnp.int32),
                        pltpu.SemaphoreType.DMA((depth + 1,))],
    )
    return pl.pallas_call(
        functools.partial(_traversal_kernel, n_vertices, tile, n_cs,
                          depth, n_blocks, max_layers, n_batch, policy),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_batch, n_words), jnp.uint32),
                   jax.ShapeDtypeStruct((n_batch, n_words), jnp.uint32),
                   jax.ShapeDtypeStruct((n_batch, v_pad), jnp.int32),
                   jax.ShapeDtypeStruct((n_batch,), jnp.int32),
                   jax.ShapeDtypeStruct((1,), jnp.int32),
                   jax.ShapeDtypeStruct((max_layers, _N_ST), jnp.int32)],
        compiler_params=compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="bfs_traversal_fused",
    )(rows, colstarts, frontier, visited, p_init)


@functools.partial(jax.jit, static_argnames=("n_vertices",
                                             "slabs_per_step", "policy",
                                             "max_layers",
                                             "prefetch_depth",
                                             "interpret"))
def sell_traversal_fused_batched(cols, slab_rows, deg, frontier,
                                 visited, p_init, *, n_vertices: int,
                                 slabs_per_step: int = 1, policy,
                                 max_layers: int = 64,
                                 prefetch_depth: int = 0,
                                 interpret: bool = True):
    """The whole multi-root SELL-C-σ traversal in ONE Pallas call.

    Same contract as `traversal_fused_batched`; the adjacency is the
    slab layout (``cols`` (n_slabs, W_QUANT, C) streamed via DMA,
    ``slab_rows`` (n_slabs, C) VMEM-resident for the in-kernel plan)
    plus the explicit ``deg`` (V,) array (SELL has no colstarts to
    derive the Table 1 edge counters from).  ``cols``/``slab_rows``
    must be pre-padded to a ``slabs_per_step`` multiple
    (`ops._pad_slabs`).
    """
    n_slabs = cols.shape[0]
    assert n_slabs % slabs_per_step == 0, \
        "pad the slab count to the step size"
    n_steps = n_slabs // slabs_per_step
    n_batch, n_words = visited.shape
    v_pad = p_init.shape[1]
    n_deg = deg.shape[0]
    depth = min(max(int(prefetch_depth), 0), n_steps)

    whole = lambda *s: pl.BlockSpec(s, lambda t: (0,) * len(s))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
                  whole(n_slabs, SLICE_C), whole(n_deg),
                  whole(n_batch, n_words), whole(n_batch, n_words),
                  whole(n_batch, v_pad)],
        out_specs=[whole(n_batch, n_words), whole(n_batch, n_words),
                   whole(n_batch, v_pad), whole(n_batch), whole(1),
                   whole(max_layers, _N_ST)],
        scratch_shapes=[pltpu.VMEM((depth + 1, slabs_per_step, W_QUANT,
                                    SLICE_C), jnp.int32),
                        pltpu.SemaphoreType.DMA((depth + 1,))],
    )
    return pl.pallas_call(
        functools.partial(_sell_traversal_kernel, n_vertices,
                          slabs_per_step, depth, n_steps, max_layers,
                          n_batch, policy),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_batch, n_words), jnp.uint32),
                   jax.ShapeDtypeStruct((n_batch, n_words), jnp.uint32),
                   jax.ShapeDtypeStruct((n_batch, v_pad), jnp.int32),
                   jax.ShapeDtypeStruct((n_batch,), jnp.int32),
                   jax.ShapeDtypeStruct((1,), jnp.int32),
                   jax.ShapeDtypeStruct((max_layers, _N_ST), jnp.int32)],
        compiler_params=compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="bfs_sell_traversal_fused",
    )(cols, slab_rows, deg, frontier, visited, p_init)
