"""Jit'd public wrappers around the Pallas BFS kernels.

Selects interpret mode through `kernels.interpret_mode` (CPU hosts run
the kernel bodies in the interpreter; a TPU compiles them), pads edge streams to
tile multiples, and enforces the VMEM budget that makes the
bitmap-in-VMEM design legal (DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.bitmap import BITS_PER_WORD
from repro.kernels import VMEM_BYTES, interpret_mode
from repro.kernels import bitmap_kernels, frontier_expand as fe
from repro.kernels import compact as ck
from repro.kernels import gather_expand as ge
from repro.kernels import layer_fused as lf
from repro.kernels import restoration as rest
from repro.kernels import sell_expand as se
from repro.kernels import traversal_fused as tf

_VMEM_HEADROOM = 0.75          # leave room for pipeline double-buffers


def vmem_limit_bytes() -> int:
    """The working-set ceiling every ``*_fits`` predicate tests
    against (VMEM minus double-buffer headroom)."""
    return int(VMEM_BYTES * _VMEM_HEADROOM)


def budget_detail(name: str, budget_bytes: int) -> str:
    """One-line human record of a failed VMEM budget — what
    `obs.metrics.record_degrade` reasons are built from, so every
    degrade log names the budget that failed in the same format."""
    return (f"{name} working set {budget_bytes / 2**20:.2f} MiB > "
            f"VMEM budget {vmem_limit_bytes() / 2**20:.1f} MiB")


# ---------------------------------------------------------------------------
# Launch accounting
# ---------------------------------------------------------------------------
# Every wrapper below charges the Pallas calls it issues to this
# module-level counter *at trace time* (the wrappers are plain Python;
# the inner kernels are jit'd).  Tracing one engine layer step under
# `count_launches()` therefore yields the exact number of Pallas
# launches that step issues per layer — the ground truth the static
# `StepAux.launches` declarations are tested against.

_LAUNCH_COUNT = [0]


def _charge_launch(n: int = 1) -> None:
    _LAUNCH_COUNT[0] += n


class count_launches:
    """Context manager counting Pallas calls traced inside the block.

    >>> with ops.count_launches() as c:
    ...     step(frontier, visited, parent)
    >>> c.count   # launches one layer of this step costs
    """
    count = 0

    def __enter__(self):
        self._base = _LAUNCH_COUNT[0]
        return self

    def __exit__(self, *exc):
        self.count = _LAUNCH_COUNT[0] - self._base
        return False


def _scoped(name: str):
    """Wrap a kernel wrapper in ``jax.named_scope`` so XLA profiles
    (`repro.obs.trace.xla_profiler` / TensorBoard) attribute device
    time to named BFS phases instead of anonymous fusions.  Trace-time
    only — zero runtime cost inside jit."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


@_scoped("bfs.expand")
def expand(nbr, cand, valid, frontier, visited, out_init, p_init, *,
           n_vertices: int, tile: int = fe.DEFAULT_TILE,
           check_frontier: bool = False, interpret: bool | None = None):
    """Pad + run the frontier-expansion kernel (top-down or bottom-up)."""
    if interpret is None:
        interpret = interpret_mode()
    budget = fe.vmem_budget(visited.shape[0], p_init.shape[0], tile)
    if budget > VMEM_BYTES * _VMEM_HEADROOM:
        raise ValueError(
            f"frontier_expand working set {budget/2**20:.1f} MiB exceeds "
            f"VMEM budget; shard the vertex range across chips "
            f"(core/bfs_distributed.py) or reduce the tile")
    n = cand.shape[0]
    pad = (-n) % tile
    if pad:
        z = jnp.zeros((pad,), jnp.int32)
        nbr = jnp.concatenate([nbr, z])
        cand = jnp.concatenate([cand, z])
        valid = jnp.concatenate([valid.astype(jnp.int32), z])
    _charge_launch()
    return fe.frontier_expand(
        nbr, cand, valid.astype(jnp.int32), frontier, visited, out_init,
        p_init, n_vertices=n_vertices, tile=tile,
        check_frontier=check_frontier, interpret=interpret)


@_scoped("bfs.expand_batched")
def expand_batched(nbr, cand, valid, frontier, visited, out_init, p_init,
                   *, n_vertices: int, tile: int = fe.DEFAULT_TILE,
                   check_frontier: bool = False,
                   interpret: bool | None = None):
    """Pad + run the batched (leading root-axis) expansion kernel.

    All arrays carry a leading (B,) root axis; each root's search
    expands independently in one launch.  The VMEM budget is per-root
    (the kernel pins one root's bitmaps/P at a time).
    """
    if interpret is None:
        interpret = interpret_mode()
    budget = fe.vmem_budget(visited.shape[1], p_init.shape[1], tile)
    if budget > VMEM_BYTES * _VMEM_HEADROOM:
        raise ValueError(
            f"frontier_expand working set {budget/2**20:.1f} MiB exceeds "
            f"VMEM budget; shard the vertex range across chips "
            f"(core/bfs_distributed.py) or reduce the tile")
    n = cand.shape[1]
    pad = (-n) % tile
    if pad:
        z = jnp.zeros((cand.shape[0], pad), jnp.int32)
        nbr = jnp.concatenate([nbr, z], axis=1)
        cand = jnp.concatenate([cand, z], axis=1)
        valid = jnp.concatenate([valid.astype(jnp.int32), z], axis=1)
    _charge_launch()
    return fe.frontier_expand_batched(
        nbr, cand, valid.astype(jnp.int32), frontier, visited, out_init,
        p_init, n_vertices=n_vertices, tile=tile,
        check_frontier=check_frontier, interpret=interpret)


def _gather_budget_check(n_words: int, v_pad: int, n_cs: int,
                         tile: int, prefetch_depth: int = 0,
                         n_blocks: int | None = None) -> None:
    budget = ge.vmem_budget(n_words, v_pad, n_cs, tile, prefetch_depth,
                            n_blocks)
    if budget > VMEM_BYTES * _VMEM_HEADROOM:
        raise ValueError(
            f"gather_expand working set {budget/2**20:.1f} MiB exceeds "
            f"VMEM budget; shard the vertex range across chips "
            f"(core/bfs_distributed.py) or reduce the tile or "
            f"prefetch_depth")


@_scoped("bfs.gather_expand")
def gather_expand(worklist, n_active, rows, colstarts, frontier,
                  visited, out_init, p_init, *, n_vertices: int,
                  tile: int = ge.DEFAULT_TILE, bottom_up: bool = False,
                  prefetch_depth: int = 0,
                  interpret: bool | None = None):
    """Run the fused in-kernel CSR gather over one layer's active
    tiles (see kernels/gather_expand.py).  ``rows`` must already be
    padded to a tile multiple (done once at build by the format, NOT
    per layer — re-padding inside the layer loop would reintroduce
    the O(E) copy this kernel exists to remove).  ``prefetch_depth``
    > 0 selects the manual double-buffered DMA input pipeline."""
    if interpret is None:
        interpret = interpret_mode()
    _gather_budget_check(visited.shape[0], p_init.shape[0],
                         colstarts.shape[0], tile, prefetch_depth,
                         rows.shape[0] // tile)
    n_active = jnp.atleast_1d(jnp.asarray(n_active, jnp.int32))
    _charge_launch()
    return ge.gather_expand(
        worklist.astype(jnp.int32), n_active, rows, colstarts, frontier,
        visited, out_init, p_init, n_vertices=n_vertices, tile=tile,
        bottom_up=bottom_up, prefetch_depth=prefetch_depth,
        interpret=interpret)


@_scoped("bfs.gather_expand_batched")
def gather_expand_batched(worklist, n_active, rows, colstarts, frontier,
                          visited, out_init, p_init, *, n_vertices: int,
                          tile: int = ge.DEFAULT_TILE,
                          bottom_up: bool = False,
                          prefetch_depth: int = 0,
                          interpret: bool | None = None):
    """Batched (leading root-axis) fused gather-expand: worklist/
    n_active/bitmaps/P carry (B, ...); the CSR arrays are shared.
    The VMEM budget is per-root."""
    if interpret is None:
        interpret = interpret_mode()
    _gather_budget_check(visited.shape[1], p_init.shape[1],
                         colstarts.shape[0], tile, prefetch_depth,
                         rows.shape[0] // tile)
    _charge_launch()
    return ge.gather_expand_batched(
        worklist.astype(jnp.int32), n_active.astype(jnp.int32), rows,
        colstarts, frontier, visited, out_init, p_init,
        n_vertices=n_vertices, tile=tile, bottom_up=bottom_up,
        prefetch_depth=prefetch_depth, interpret=interpret)


@_scoped("bfs.gather_relax_batched")
def gather_relax_batched(worklist, n_active, rows, colstarts, frontier,
                         vals, *, n_vertices: int,
                         tile: int = ge.DEFAULT_TILE, unit: int = 0,
                         weighted: bool = False,
                         interpret: bool | None = None):
    """Batched semiring relaxation over the active CSR tiles
    (kernels/gather_expand.py `gather_relax_batched`): scatter-min of
    ``vals[u] ⊗ w`` candidates plus the phase-2 deterministic parent
    resolve.  Per-root VMEM working set: frontier words + 2 value rows
    + the parent row + colstarts + the double-buffered rows tiles."""
    if interpret is None:
        interpret = interpret_mode()
    n_words, v_pad = frontier.shape[1], vals.shape[1]
    budget = 4 * (n_words + 3 * v_pad + colstarts.shape[0]) \
        + 2 * 4 * tile
    if budget > VMEM_BYTES * _VMEM_HEADROOM:
        raise ValueError(
            f"gather_relax working set {budget/2**20:.1f} MiB exceeds "
            f"VMEM budget; shard the vertex range across chips "
            f"(core/bfs_distributed.py) or reduce the tile")
    _charge_launch()
    return ge.gather_relax_batched(
        worklist.astype(jnp.int32), n_active.astype(jnp.int32), rows,
        colstarts, frontier, vals, n_vertices=n_vertices, tile=tile,
        unit=unit, weighted=weighted, interpret=interpret)


def _pad_slabs(cols, slab_rows, n_vertices: int, step: int):
    """Pad the slab axis to a multiple of ``step`` with sentinel slabs
    (all-V neighbor ids and row ids mask out entirely in-kernel)."""
    n_slabs = cols.shape[0]
    pad = (-n_slabs) % step
    if pad:
        cols = jnp.concatenate(
            [cols, jnp.full((pad,) + cols.shape[1:], n_vertices,
                            jnp.int32)])
        slab_rows = jnp.concatenate(
            [slab_rows, jnp.full((pad, slab_rows.shape[1]), n_vertices,
                                 jnp.int32)])
    return cols, slab_rows


def _sell_budget_check(n_words: int, v_pad: int, step: int,
                       prefetch_depth: int = 0,
                       n_steps: int | None = None) -> None:
    budget = se.vmem_budget(n_words, v_pad, step, prefetch_depth,
                            n_steps)
    if budget > VMEM_BYTES * _VMEM_HEADROOM:
        raise ValueError(
            f"sell_expand working set {budget/2**20:.1f} MiB exceeds "
            f"VMEM budget; shard the vertex range across chips "
            f"(core/bfs_distributed.py) or reduce slabs_per_step or "
            f"prefetch_depth")


@_scoped("bfs.sell")
def sell(cols, slab_rows, frontier, visited, out_init, p_init, *,
         n_vertices: int, slabs_per_step: int = 1, worklist=None,
         n_active=None, bottom_up: bool = False,
         prefetch_depth: int = 0, interpret: bool | None = None):
    """Pad + run the single-root SELL-C-σ sweep kernel.

    ``worklist``/``n_active`` schedule the active slab groups (the
    fused pipeline; `formats.sell.SellFormat` plans them); omitting
    both runs the full identity sweep (the materialized pipeline).
    ``bottom_up`` swaps the sweep's gate/discover roles (rows are
    discovered, neighbors tested against the frontier);
    ``prefetch_depth`` > 0 selects the manual double-buffered DMA
    input pipeline.
    """
    if interpret is None:
        interpret = interpret_mode()
    _sell_budget_check(visited.shape[0], p_init.shape[0],
                       slabs_per_step, prefetch_depth,
                       -(-cols.shape[0] // slabs_per_step))
    cols, slab_rows = _pad_slabs(cols, slab_rows, n_vertices,
                                 slabs_per_step)
    n_steps = cols.shape[0] // slabs_per_step
    if worklist is None:
        worklist = jnp.arange(n_steps, dtype=jnp.int32)
        n_active = jnp.full((1,), n_steps, jnp.int32)
    else:
        n_active = jnp.atleast_1d(jnp.asarray(n_active, jnp.int32))
    _charge_launch()
    return se.sell_expand(
        cols, slab_rows, worklist.astype(jnp.int32), n_active, frontier,
        visited, out_init, p_init, n_vertices=n_vertices,
        slabs_per_step=slabs_per_step, bottom_up=bottom_up,
        prefetch_depth=prefetch_depth, interpret=interpret)


@_scoped("bfs.sell_batched")
def sell_batched(cols, slab_rows, frontier, visited, out_init, p_init,
                 *, n_vertices: int, slabs_per_step: int = 1,
                 worklist=None, n_active=None, bottom_up: bool = False,
                 prefetch_depth: int = 0,
                 interpret: bool | None = None):
    """Pad + run the batched (leading root-axis) SELL-C-σ sweep.

    The adjacency slabs carry no root axis (the layout is shared);
    bitmaps/P are (B, W) / (B, V_pad); per-root ``worklist`` is
    (B, n_steps) with ``n_active`` (B,) — omitted = full sweep for
    every root.  VMEM budget is per-root.
    """
    if interpret is None:
        interpret = interpret_mode()
    _sell_budget_check(visited.shape[1], p_init.shape[1],
                       slabs_per_step, prefetch_depth,
                       -(-cols.shape[0] // slabs_per_step))
    cols, slab_rows = _pad_slabs(cols, slab_rows, n_vertices,
                                 slabs_per_step)
    n_steps = cols.shape[0] // slabs_per_step
    n_batch = visited.shape[0]
    if worklist is None:
        worklist = jnp.broadcast_to(jnp.arange(n_steps, dtype=jnp.int32),
                                    (n_batch, n_steps))
        n_active = jnp.full((n_batch,), n_steps, jnp.int32)
    _charge_launch()
    return se.sell_expand_batched(
        cols, slab_rows, worklist.astype(jnp.int32),
        n_active.astype(jnp.int32), frontier, visited, out_init, p_init,
        n_vertices=n_vertices, slabs_per_step=slabs_per_step,
        bottom_up=bottom_up, prefetch_depth=prefetch_depth,
        interpret=interpret)


@_scoped("bfs.sell_relax_batched")
def sell_relax_batched(cols, slab_rows, worklist, n_active, frontier,
                       vals, *, n_vertices: int, slabs_per_step: int = 1,
                       unit: int = 0, weighted: bool = False,
                       interpret: bool | None = None):
    """Batched semiring SpMV sweep over the active SELL slab groups
    (kernels/sell_expand.py `sell_relax_batched`).  Pads the slab axis
    itself; the per-root work-list contract matches `sell_batched`."""
    if interpret is None:
        interpret = interpret_mode()
    n_words, v_pad = frontier.shape[1], vals.shape[1]
    slab = slabs_per_step * (se.W_QUANT + 1) * se.SLICE_C * 4
    budget = 4 * (n_words + 3 * v_pad) + 2 * slab
    if budget > VMEM_BYTES * _VMEM_HEADROOM:
        raise ValueError(
            f"sell_relax working set {budget/2**20:.1f} MiB exceeds "
            f"VMEM budget; shard the vertex range across chips "
            f"(core/bfs_distributed.py) or reduce slabs_per_step")
    cols, slab_rows = _pad_slabs(cols, slab_rows, n_vertices,
                                 slabs_per_step)
    _charge_launch()
    return se.sell_relax_batched(
        cols, slab_rows, worklist.astype(jnp.int32),
        n_active.astype(jnp.int32), frontier, vals,
        n_vertices=n_vertices, slabs_per_step=slabs_per_step, unit=unit,
        weighted=weighted, interpret=interpret)


@_scoped("bfs.restore")
def restore(parent, *, n_vertices: int, tile: int = rest.DEFAULT_TILE,
            interpret: bool | None = None):
    """Run the restoration kernel over a (V_pad,) or batched
    (B, V_pad) parent; the delta bitmap comes back as (W,) / (B, W).

    Restoration is element-wise, so the batch flattens through one
    launch, zero-padded up to a tile multiple (a zero is an unmarked
    parent) and sliced back, so no V_pad is too odd for the tile.
    """
    if interpret is None:
        interpret = interpret_mode()
    _charge_launch()
    flat = parent.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % tile
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    p, delta = rest.restoration(flat, n_vertices=n_vertices, tile=tile,
                                interpret=interpret)
    words = parent.shape[:-1] + (parent.shape[-1] // BITS_PER_WORD,)
    return (p[:n].reshape(parent.shape),
            delta[:n // BITS_PER_WORD].reshape(words))


@_scoped("bfs.popcount")
def popcount(words, *, interpret: bool | None = None):
    if interpret is None:
        interpret = interpret_mode()
    _charge_launch()
    return bitmap_kernels.popcount(words, interpret=interpret)


def compact_budget(n_batch: int, size: int) -> int:
    """Bytes the compaction kernel's (B, size) queue block pins in
    VMEM — the number `compact_fits` tests and degrade events report."""
    return ck.vmem_budget(n_batch, size, ck.DEFAULT_TILE_WORDS)


def compact_fits(n_batch: int, size: int) -> bool:
    """True when the compaction kernel's (B, size) queue block fits
    the VMEM budget.  The engine's packed planning arms consult this
    at trace time and fall back to the dense planner when it is False
    — large graphs keep working exactly as they did before the packed
    default, instead of failing on the budget check.  Since ISSUE 8
    the fallback is *observable*: every caller that degrades emits a
    ``serve.degrade.vmem_fallback`` `obs.metrics.DegradeEvent` naming
    this budget and the planner actually used."""
    return compact_budget(n_batch, size) <= VMEM_BYTES * _VMEM_HEADROOM


@_scoped("bfs.frontier_compact")
def frontier_compact(words, *, size: int, fill: int,
                     interpret: bool | None = None):
    """Run the SIMD compaction kernel (kernels/compact.py): packed
    bitmap -> (dense vertex queue (size,), count).  The packed
    replacement for `bitmap.compact` + `bitmap.popcount`."""
    if interpret is None:
        interpret = interpret_mode()
    _charge_launch()
    return ck.frontier_compact(words, size=size, fill=fill,
                               interpret=interpret)


@_scoped("bfs.frontier_compact_batched")
def frontier_compact_batched(words, *, size: int, fill: int,
                             interpret: bool | None = None):
    """Batched compaction: (B, W) packed bitmaps -> ((B, size)
    queues, (B,) counts) in one launch."""
    if interpret is None:
        interpret = interpret_mode()
    _charge_launch()
    return ck.frontier_compact_batched(words, size=size, fill=fill,
                                       interpret=interpret)


def megakernel_budget(n_words: int, v_pad: int, n_cs: int, tile: int,
                      prefetch_depth: int, n_blocks: int) -> int:
    """Bytes the whole-layer megakernel pins in VMEM — the number
    `megakernel_fits` tests and degrade events report."""
    return lf.vmem_budget(n_words, v_pad, n_cs, tile, prefetch_depth,
                          n_blocks)


_megakernel_budget = megakernel_budget    # back-compat alias


def megakernel_fits(n_words: int, v_pad: int, n_cs: int, tile: int,
                    prefetch_depth: int = 0, n_blocks: int = 1) -> bool:
    """True when the whole-layer megakernel's working set (bitmaps +
    P + colstarts + rows DMA buffers + the in-kernel planning
    vectors) fits the VMEM budget.  `CsrFormat._build_steps` consults
    this at build time and degrades ``pipeline="megakernel"`` to the
    unfused ``fused_gather`` step when it is False — mirroring
    `compact_fits`: large graphs keep traversing (at the unfused
    launch count) instead of failing on the budget check.  Since
    ISSUE 8 the degrade emits a ``serve.degrade.vmem_fallback``
    `obs.metrics.DegradeEvent` naming this budget and the pipeline
    actually built."""
    return megakernel_budget(n_words, v_pad, n_cs, tile,
                             prefetch_depth, n_blocks) \
        <= VMEM_BYTES * _VMEM_HEADROOM


@_scoped("bfs.layer_fused")
def layer_fused(rows, colstarts, frontier, visited, p_init, *,
                n_vertices: int, tile: int = ge.DEFAULT_TILE,
                bottom_up: bool = False, prefetch_depth: int = 0,
                interpret: bool | None = None):
    """Run one whole BFS layer (plan + compact + gather-expand +
    restoration) in ONE Pallas call (kernels/layer_fused.py).
    ``rows`` must already be padded to a tile multiple at build.
    Returns (out, parent, n_active) with restoration APPLIED."""
    if interpret is None:
        interpret = interpret_mode()
    n_blocks = rows.shape[0] // tile
    budget = _megakernel_budget(visited.shape[0], p_init.shape[0],
                                colstarts.shape[0], tile,
                                prefetch_depth, n_blocks)
    if budget > VMEM_BYTES * _VMEM_HEADROOM:
        raise ValueError(
            f"layer_fused working set {budget/2**20:.1f} MiB exceeds "
            f"VMEM budget; shard the vertex range across chips "
            f"(core/bfs_distributed.py), reduce the tile or "
            f"prefetch_depth, or run pipeline='fused_gather'")
    _charge_launch()
    return lf.layer_fused(
        rows, colstarts, frontier, visited, p_init,
        n_vertices=n_vertices, tile=tile, bottom_up=bottom_up,
        prefetch_depth=prefetch_depth, interpret=interpret)


@_scoped("bfs.layer_fused_batched")
def layer_fused_batched(rows, colstarts, frontier, visited, p_init, *,
                        n_vertices: int, tile: int = ge.DEFAULT_TILE,
                        bottom_up: bool = False, prefetch_depth: int = 0,
                        interpret: bool | None = None):
    """Batched (leading root-axis) whole-layer megakernel: one launch,
    B restored layers.  The VMEM budget is per-root."""
    if interpret is None:
        interpret = interpret_mode()
    n_blocks = rows.shape[0] // tile
    budget = _megakernel_budget(visited.shape[1], p_init.shape[1],
                                colstarts.shape[0], tile,
                                prefetch_depth, n_blocks)
    if budget > VMEM_BYTES * _VMEM_HEADROOM:
        raise ValueError(
            f"layer_fused working set {budget/2**20:.1f} MiB exceeds "
            f"VMEM budget; shard the vertex range across chips "
            f"(core/bfs_distributed.py), reduce the tile or "
            f"prefetch_depth, or run pipeline='fused_gather'")
    _charge_launch()
    return lf.layer_fused_batched(
        rows, colstarts, frontier, visited, p_init,
        n_vertices=n_vertices, tile=tile, bottom_up=bottom_up,
        prefetch_depth=prefetch_depth, interpret=interpret)


def sell_megakernel_budget(n_words: int, v_pad: int, n_slabs: int,
                           slabs_per_step: int, prefetch_depth: int = 0
                           ) -> int:
    """Bytes the whole-layer SELL megakernel pins in VMEM — the
    number `sell_megakernel_fits` tests and degrade events report.
    ``n_slabs`` is the raw slab count; step padding and the pipeline
    depth clamp are resolved here (budgets from the resolved spec)."""
    n_steps = -(-int(n_slabs) // int(slabs_per_step))
    n_slabs_p = n_steps * int(slabs_per_step)
    return se.megakernel_vmem_budget(n_words, v_pad, n_slabs_p,
                                     slabs_per_step, prefetch_depth,
                                     n_steps)


def sell_megakernel_fits(n_words: int, v_pad: int, n_slabs: int,
                         slabs_per_step: int,
                         prefetch_depth: int = 0) -> bool:
    """True when the whole-layer SELL megakernel (resident
    ``slab_rows`` + cols DMA buffers + bitmaps/P) fits the VMEM
    budget.  `SellFormat._build_steps` consults this at build time and
    degrades to the unfused ``fused_gather`` steps when False, with a
    metric-counted `DegradeEvent` — the `megakernel_fits` contract."""
    return sell_megakernel_budget(n_words, v_pad, n_slabs,
                                  slabs_per_step, prefetch_depth) \
        <= VMEM_BYTES * _VMEM_HEADROOM


@_scoped("bfs.sell_layer_fused")
def sell_layer_fused(cols, slab_rows, frontier, visited, p_init, *,
                     n_vertices: int, slabs_per_step: int = 1,
                     bottom_up: bool = False, prefetch_depth: int = 0,
                     interpret: bool | None = None):
    """Run one whole SELL layer (in-kernel slab plan + manual cols DMA
    + sweep + restoration) in ONE Pallas call
    (kernels/sell_expand.py `sell_layer_fused`).  Pads the slab axis
    itself.  Returns (out, parent, n_active) with restoration
    APPLIED."""
    if interpret is None:
        interpret = interpret_mode()
    budget = sell_megakernel_budget(visited.shape[0], p_init.shape[0],
                                    cols.shape[0], slabs_per_step,
                                    prefetch_depth)
    if budget > VMEM_BYTES * _VMEM_HEADROOM:
        raise ValueError(
            f"sell_layer_fused working set {budget/2**20:.1f} MiB "
            f"exceeds VMEM budget; shard the vertex range across chips "
            f"(core/bfs_distributed.py), reduce slabs_per_step or "
            f"prefetch_depth, or run pipeline='fused_gather'")
    cols, slab_rows = _pad_slabs(cols, slab_rows, n_vertices,
                                 slabs_per_step)
    _charge_launch()
    return se.sell_layer_fused(
        cols, slab_rows, frontier, visited, p_init,
        n_vertices=n_vertices, slabs_per_step=slabs_per_step,
        bottom_up=bottom_up, prefetch_depth=prefetch_depth,
        interpret=interpret)


@_scoped("bfs.sell_layer_fused_batched")
def sell_layer_fused_batched(cols, slab_rows, frontier, visited,
                             p_init, *, n_vertices: int,
                             slabs_per_step: int = 1,
                             bottom_up: bool = False,
                             prefetch_depth: int = 0,
                             interpret: bool | None = None):
    """Batched (leading root-axis) whole-layer SELL megakernel: one
    launch, B restored layers.  The VMEM budget is per-root."""
    if interpret is None:
        interpret = interpret_mode()
    budget = sell_megakernel_budget(visited.shape[1], p_init.shape[1],
                                    cols.shape[0], slabs_per_step,
                                    prefetch_depth)
    if budget > VMEM_BYTES * _VMEM_HEADROOM:
        raise ValueError(
            f"sell_layer_fused working set {budget/2**20:.1f} MiB "
            f"exceeds VMEM budget; shard the vertex range across chips "
            f"(core/bfs_distributed.py), reduce slabs_per_step or "
            f"prefetch_depth, or run pipeline='fused_gather'")
    cols, slab_rows = _pad_slabs(cols, slab_rows, n_vertices,
                                 slabs_per_step)
    _charge_launch()
    return se.sell_layer_fused_batched(
        cols, slab_rows, frontier, visited, p_init,
        n_vertices=n_vertices, slabs_per_step=slabs_per_step,
        bottom_up=bottom_up, prefetch_depth=prefetch_depth,
        interpret=interpret)


def persistent_budget(n_words: int, v_pad: int, n_cs: int, tile: int,
                      n_batch: int, max_layers: int,
                      prefetch_depth: int = 0,
                      n_blocks: int = 1) -> int:
    """Bytes the CSR whole-traversal persistent kernel pins in VMEM —
    the number `persistent_fits` tests and degrade events report.
    Unlike the per-layer kernels the whole batch's state is resident
    at once, so the budget scales with ``n_batch``."""
    return tf.vmem_budget(n_words, v_pad, n_cs, tile, n_batch,
                          max_layers, prefetch_depth, n_blocks)


def persistent_fits(n_words: int, v_pad: int, n_cs: int, tile: int,
                    n_batch: int, max_layers: int,
                    prefetch_depth: int = 0, n_blocks: int = 1) -> bool:
    """True when the CSR persistent kernel's whole-batch working set
    (state x2 + colstarts + plan vectors + rows DMA buffers + stats)
    fits the VMEM budget.  The engine consults this at trace time and
    degrades ``pipeline="persistent"`` to megakernel (then unfused)
    when False, emitting a metric-counted `DegradeEvent` per the
    ISSUE 8 contract."""
    return persistent_budget(n_words, v_pad, n_cs, tile, n_batch,
                             max_layers, prefetch_depth, n_blocks) \
        <= VMEM_BYTES * _VMEM_HEADROOM


def sell_persistent_budget(n_words: int, v_pad: int, n_slabs: int,
                           slabs_per_step: int, n_batch: int,
                           max_layers: int,
                           prefetch_depth: int = 0) -> int:
    """Bytes the SELL whole-traversal persistent kernel pins in VMEM
    (resident ``slab_rows`` + degrees + cols DMA buffers + the whole
    batch's state)."""
    n_steps = -(-int(n_slabs) // int(slabs_per_step))
    n_slabs_p = n_steps * int(slabs_per_step)
    return tf.sell_vmem_budget(n_words, v_pad, n_slabs_p,
                               slabs_per_step, n_batch, max_layers,
                               prefetch_depth, n_steps)


def sell_persistent_fits(n_words: int, v_pad: int, n_slabs: int,
                         slabs_per_step: int, n_batch: int,
                         max_layers: int,
                         prefetch_depth: int = 0) -> bool:
    """`persistent_fits` for the SELL persistent kernel."""
    return sell_persistent_budget(n_words, v_pad, n_slabs,
                                  slabs_per_step, n_batch, max_layers,
                                  prefetch_depth) \
        <= VMEM_BYTES * _VMEM_HEADROOM


@_scoped("bfs.traversal_fused")
def traversal_fused_batched(rows, colstarts, frontier, visited, p_init,
                            *, n_vertices: int,
                            tile: int = ge.DEFAULT_TILE, policy,
                            max_layers: int = 64,
                            prefetch_depth: int = 0,
                            interpret: bool | None = None):
    """Run the WHOLE multi-root BFS traversal in ONE Pallas call
    (kernels/traversal_fused.py): layer loop, direction decision and
    termination all inside the kernel, state VMEM-resident across
    layers.  ``rows`` must already be padded to a tile multiple.
    Returns (frontier, visited, parent, depths, layers, stats) — the
    engine's whole-traversal contract — and charges exactly ONE launch
    to the trace-time counter."""
    if interpret is None:
        interpret = interpret_mode()
    n_blocks = rows.shape[0] // tile
    budget = persistent_budget(visited.shape[1], p_init.shape[1],
                               colstarts.shape[0], tile,
                               visited.shape[0], max_layers,
                               prefetch_depth, n_blocks)
    if budget > VMEM_BYTES * _VMEM_HEADROOM:
        raise ValueError(
            f"traversal_fused working set {budget/2**20:.1f} MiB "
            f"exceeds VMEM budget; reduce the batch width, the tile "
            f"or max_layers, or run pipeline='megakernel'")
    _charge_launch()
    return tf.traversal_fused_batched(
        rows, colstarts, frontier, visited, p_init,
        n_vertices=n_vertices, tile=tile, policy=policy,
        max_layers=max_layers, prefetch_depth=prefetch_depth,
        interpret=interpret)


@_scoped("bfs.sell_traversal_fused")
def sell_traversal_fused_batched(cols, slab_rows, deg, frontier,
                                 visited, p_init, *, n_vertices: int,
                                 slabs_per_step: int = 1, policy,
                                 max_layers: int = 64,
                                 prefetch_depth: int = 0,
                                 interpret: bool | None = None):
    """The whole multi-root SELL traversal in ONE Pallas call.  Pads
    the slab axis itself; ``deg`` is the (V,) degree array (SELL has
    no colstarts for the in-kernel Table 1 counters).  Same contract
    and launch accounting as `traversal_fused_batched`."""
    if interpret is None:
        interpret = interpret_mode()
    budget = sell_persistent_budget(visited.shape[1], p_init.shape[1],
                                    cols.shape[0], slabs_per_step,
                                    visited.shape[0], max_layers,
                                    prefetch_depth)
    if budget > VMEM_BYTES * _VMEM_HEADROOM:
        raise ValueError(
            f"sell_traversal_fused working set {budget/2**20:.1f} MiB "
            f"exceeds VMEM budget; reduce the batch width, "
            f"slabs_per_step or max_layers, or run "
            f"pipeline='megakernel'")
    cols, slab_rows = _pad_slabs(cols, slab_rows, n_vertices,
                                 slabs_per_step)
    _charge_launch()
    return tf.sell_traversal_fused_batched(
        cols, slab_rows, deg, frontier, visited, p_init,
        n_vertices=n_vertices, slabs_per_step=slabs_per_step,
        policy=policy, max_layers=max_layers,
        prefetch_depth=prefetch_depth, interpret=interpret)
