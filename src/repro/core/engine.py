"""Unified on-device BFS traversal engine with pluggable direction policies.

DESIGN
======
Every BFS variant in this repo — Algorithms 2/3 of the paper, the §4
vectorized pipeline, the Beamer-style hybrid, and the distributed
per-chip program — is the same per-layer pipeline:

    measure workload  ->  decide direction  ->  expand  ->  restore

This module is the single home of that pipeline.  The paper sections
map onto engine phases as follows:

* **measure** (`Workload`): §4.1's layer-adaptive decision input — the
  frontier vertex/edge counts of Table 1, computed *on device* from the
  bitmap (§3.3.1) and the CSR degree array.
* **decide** (`DirectionPolicy.decide`): which expansion flavour runs
  this layer.  ``MODE_SCALAR`` is the plain-jnp Algorithm 2/3 layer,
  ``MODE_SIMD`` the §4 Pallas kernel (Listing 1), ``MODE_BOTTOMUP`` the
  frontier-testing kernel of the hybrid extension (arXiv:1704.02259).
  Policies are small frozen objects deciding from on-device counters,
  so the decision traces into the fused loop — no host round-trip.
* **expand**: the racy gather-test-mask-scatter hot loop (§3.2, §3.3.2
  Fig. 6).  Two pipelines exist (the ``pipeline`` axis):

  - ``fused_gather`` (default, ISSUE 3) — HBM traffic proportional to
    the live frontier: a tiny on-device planning pass
    (`plan_active_tiles`) builds a work-list of the rows-blocks the
    frontier's adjacency touches, and the kernel
    (kernels/gather_expand.py) gathers candidate edges HBM->VMEM
    in-kernel, recomputing edge->owner with a binary search over the
    VMEM-resident ``colstarts``.  Inactive tiles are clamped to a
    sentinel block by the scalar-prefetched index map (the DMA is
    elided) and skipped by a ``pl.when`` guard, so a thin layer costs
    ~1 tile instead of E_pad/tile tiles.
  - ``materialized`` (legacy, kept for the ablation axis) — the
    apportionment machinery (`edge_stream`) writes a full-E ``(u, v,
    valid)`` stream to HBM which the kernel then re-reads.
  - ``xla`` — the shared `expand_candidates` body as XLA ops over
    the whole edge stream, no Pallas kernel (`make_xla_steps`): per
    slot and layer two word gathers (the owner gate, the visited
    test) and one parent scatter.  The TPU compiler refuses the
    expansion kernels of the other pipelines, so on a TPU this is the
    one the formats declare (``GraphFormat.tpu_pipelines``).

  The scalar (plain-jnp) layer keeps the materialized apportionment in
  both pipelines; the batched kernels add a leading root axis so many
  searches expand in one launch.
* **restore** (§3.3.2, Alg. 3 lines 15-29): every vertex discovered
  this layer is identified by its negative ``P`` entry and its bit is
  re-set exactly — what makes the non-atomic vectorization legal.
  The Pallas kernels repair their racy bitmap from these marks
  (`ops.restore`); the jnp body (`expand_candidates`) writes no racy
  bitmap at all and packs the new frontier from the marks alone.

Two drivers expose the pipeline:

* ``traverse``          — the **fused** engine: the whole search (all
  layers, all roots) is ONE ``lax.while_loop`` over statically padded
  buffers.  No host synchronization inside the layer loop; per-layer
  stats (Table 1 counters + chosen mode) are written into a preallocated
  on-device buffer and read back once after the loop.  Supports batched
  multi-root search via a leading root axis on every state array.
* ``traverse_hostloop``  — the legacy Python layer loop with
  power-of-two shape buckets (exact per-layer shapes, a few recompiles).
  Kept for A/B measurement of the removed layer-loop overhead
  (benchmarks/bfs_batched.py) and for workload studies.

The public drivers ``bfs_parallel.run_bfs``,
``bfs_vectorized.run_bfs_vectorized`` and ``bfs_hybrid.run_bfs_hybrid``
are thin wrappers selecting a policy; ``bfs_distributed`` builds its
shard_map per-chip step from `rowsweep_stream` + `candidate_scatter`.

The engine is **format-generic** (repro/formats/): the per-layer
expansion steps are built by the graph format object — CSR keeps the
apportioned edge stream below, SELL-C-σ substitutes its aligned slab
sweep (kernels/sell_expand.py), the bitmap layout its dense word
sweep.  `traverse` accepts a `Csr` or any built `GraphFormat`; the
measure/decide/restore pipeline is layout-independent.

Since ISSUE 4 packed uint32 words are the engine's **native**
frontier/visited representation through the whole layer, not just at
rest: workload counters come from word popcounts and the word-aligned
degree matrix (`bitmap.masked_degree_sum`), and every bitmap->queue
conversion (planning, apportionment input lists, bottom-up candidate
lists) runs the SIMD compaction kernel (kernels/compact.py — the §4
vectorized queue generation) instead of a dense ``unpack``/``nonzero``
round trip.  The legacy dense-mask arm survives behind
``packed=False`` as the parity/ablation baseline; ``prefetch_depth``
selects the gather kernels' manual double-buffered DMA input pipeline
(§4's prefetch distance as an explicit knob).
"""
from __future__ import annotations

import functools
import operator
import warnings
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmap as bm
from repro.core.csr import Csr, init_visited, padding_premarked_visited
from repro.kernels import interpret_mode, ops

MODE_SCALAR = 0     # plain-jnp Algorithm 2/3 layer
MODE_SIMD = 1       # §4 Pallas expansion kernel (top-down)
MODE_BOTTOMUP = 2   # frontier-testing kernel (hybrid bottom-up)

MODE_NAMES = {MODE_SCALAR: "topdown", MODE_SIMD: "topdown",
              MODE_BOTTOMUP: "bottomup"}

PIPELINES = ("fused_gather", "materialized", "megakernel",
             "persistent", "xla")


def _record_degrade(site: str, reason: str, fallback: str,
                    detail: str = ""):
    """Emit an observable `obs.metrics.DegradeEvent` from a fallback
    decision (ISSUE 8).  Imported lazily: `repro.obs` pulls the plan
    layer at package-import time, which pulls this module — the
    runtime call happens long after both are loaded, so the lazy form
    is cycle-free where a top-level import would not be."""
    from repro.obs.metrics import record_degrade
    return record_degrade(site, reason, fallback, detail)

# on-device per-layer stats buffer columns
(_ST_FRONTIER, _ST_EDGES, _ST_DISCOVERED, _ST_MODE, _ST_ACTIVE,
 _ST_TILES, _ST_TRUNC, _ST_LAUNCH) = range(8)
_N_ST = 8


class BfsState(NamedTuple):
    frontier: jax.Array     # input bitmap (W,) uint32 — (B, W) batched
    visited: jax.Array      # visited bitmap (W,) uint32
    parent: jax.Array       # P, (V_pad,) int32; init = V ("infinity")
    layer: jax.Array        # scalar int32


class LayerStats(NamedTuple):
    layer: int
    frontier_vertices: int  # |in|  (Table 1 "Vertices")
    edges_examined: int     # Σ deg(in)  (Table 1 "Edges")
    discovered: int         # |out| (Table 1 "Traversed vertices")
    active_tiles: int = 0   # grid tiles of real work this layer
    #                         (batch-summed; the fused pipeline's
    #                         frontier-proportionality counter)
    truncated_edges: int = 0  # edges clamped by apportionment overflow
    launches: int = 0       # Pallas calls this layer issued (ISSUE 6:
    #                         megakernel = 1, fused_gather = 3, ...)


class StepAux(NamedTuple):
    """Per-layer accounting every format step returns with its state.

    ``tiles`` is the number of grid tiles (DMA units) of real work the
    step scheduled, summed over the root batch — the analytic
    bytes-moved counter that makes the fused pipeline's win visible in
    CI even in interpret mode.  ``truncated`` counts edges the
    apportionment clamped (hub-overflow; 0 on the fused path, which
    never apportions).  ``launches`` is the number of Pallas calls the
    step issues per layer — counted at trace time by wrapping the step
    body in `ops.count_launches`, so the figure is the measured ground
    truth, not a declaration that can drift (the megakernel's
    fusion win: 1 vs the unfused pipeline's 3)."""
    tiles: jax.Array        # int32 scalar
    truncated: jax.Array    # int32 scalar
    launches: jax.Array | int = 0  # int32 scalar (static per step)


class Workload(NamedTuple):
    """On-device counters a `DirectionPolicy` decides from (§4.1).

    In batched mode the counters are summed over the root batch **in
    float32**: per-root edge counts are int32-bounded (E < 2^31, the
    CSR invariant), but a batch of B roots can sum past 2^31; policies
    only take ratios/thresholds of these, so float32 precision is
    ample.  ``n_roots`` lets per-graph thresholds (Beamer's V/beta)
    scale to the batch.
    """
    layer: jax.Array                 # int32 scalar
    frontier_vertices: jax.Array     # scalar (batch-summed, may be f32)
    frontier_edges: jax.Array        # scalar (batch-summed, may be f32)
    unvisited_vertices: jax.Array    # scalar (0 unless needed)
    unvisited_edges: jax.Array       # scalar
    n_vertices: int                  # static |V|
    bottom_up: jax.Array             # bool scalar, previous direction
    n_roots: int = 1                 # static batch width


class EngineResult(NamedTuple):
    state: BfsState          # final state; batched arrays iff multi-root
    depths: jax.Array        # (B,) int32: layers each root stayed active
    stats: jax.Array         # (max_layers, _N_ST) int32 device buffer
    values: jax.Array | None = None  # semiring value matrix (B, V_pad)
    #                          — distances/labels/depth rows for the
    #                          algorithm portfolio (ISSUE 10); None on
    #                          the hard-wired BFS paths


# ---------------------------------------------------------------------------
# Direction policies
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TopDown:
    """Always the scalar top-down layer (Algorithms 2/3)."""
    modes = (MODE_SCALAR,)
    needs_unvisited = False

    def decide(self, w: Workload):
        return jnp.int32(MODE_SCALAR), jnp.asarray(False)


@dataclass(frozen=True)
class ThresholdSimd:
    """§4.1 adaptive policy: SIMD kernel on layers examining at least
    ``simd_threshold`` edges, scalar elsewhere."""
    simd_threshold: int = 16_384
    modes = (MODE_SCALAR, MODE_SIMD)
    needs_unvisited = False

    def decide(self, w: Workload):
        mode = jnp.where(w.frontier_edges >= self.simd_threshold,
                         MODE_SIMD, MODE_SCALAR)
        return mode.astype(jnp.int32), jnp.asarray(False)


@dataclass(frozen=True)
class PaperLiteralLayers:
    """The paper's literal §4.1 policy: SIMD on an explicit layer set
    (the "first two [fat] layers"), scalar elsewhere."""
    simd_layers: tuple[int, ...] = (1, 2)
    modes = (MODE_SCALAR, MODE_SIMD)
    needs_unvisited = False

    def decide(self, w: Workload):
        hit = functools.reduce(
            operator.or_, [w.layer == l for l in self.simd_layers],
            jnp.asarray(False))
        mode = jnp.where(hit, MODE_SIMD, MODE_SCALAR)
        return mode.astype(jnp.int32), jnp.asarray(False)


@dataclass(frozen=True)
class BeamerHybrid:
    """Direction-optimizing switch [Beamer 2012] with hysteresis:
    down when the frontier's out-edges exceed unexplored/alpha, back up
    when the frontier shrinks below V/beta.  Top-down layers use the
    SIMD kernel (the arXiv:1704.02259 hybrid vectorization)."""
    alpha: float = 14.0
    beta: float = 24.0
    modes = (MODE_SIMD, MODE_BOTTOMUP)
    needs_unvisited = True

    def decide(self, w: Workload):
        f_edges = w.frontier_edges.astype(jnp.float32)
        u_edges = w.unvisited_edges.astype(jnp.float32)
        f_count = w.frontier_vertices.astype(jnp.float32)
        switch_down = (~w.bottom_up) & (f_edges > u_edges / self.alpha)
        # V/beta scales by the batch width: counters are batch-summed
        switch_up = w.bottom_up & (
            f_count < w.n_vertices * w.n_roots / self.beta)
        bottom_up = jnp.where(switch_down, True,
                              jnp.where(switch_up, False, w.bottom_up))
        mode = jnp.where(bottom_up & (w.unvisited_vertices > 0),
                         MODE_BOTTOMUP, MODE_SIMD)
        return mode.astype(jnp.int32), bottom_up


# ---------------------------------------------------------------------------
# Shared per-layer building blocks
# ---------------------------------------------------------------------------

def apportion(csr_colstarts: jax.Array, csr_rows: jax.Array,
              frontier_list: jax.Array, n_vertices: int, n_slots: int):
    """Map ``n_slots`` edge slots onto the frontier's adjacency lists.

    frontier_list is sentinel-padded (id == n_vertices => empty).
    Returns (u, v, valid, truncated) — the streams are length n_slots;
    ``truncated`` is the int32 count of edges that did NOT fit (a hub
    whose adjacency overruns the remaining slots is clamped
    *deterministically* to its list prefix — the clip below — instead
    of silently corrupting owners; the counter surfaces the loss in
    `LayerStats.truncated_edges`).

    Owner lookup is a scatter + prefix-sum instead of a binary search:
    ``owner[slot] = #frontier vertices whose adjacency ends at or
    before slot`` = cumsum of end-offset markers.  A vectorized
    searchsorted lowers to a log2(F)-iteration while loop that re-reads
    the full slot array every pass (measured 16.3 GB/layer at SCALE-27
    per chip); the prefix-sum form is two passes (§Perf iteration 2).
    """
    is_real = frontier_list < n_vertices
    safe = jnp.where(is_real, frontier_list, 0)
    deg = jnp.where(is_real,
                    csr_colstarts[safe + 1] - csr_colstarts[safe], 0)
    cum = jnp.cumsum(deg, dtype=jnp.int32)
    total = cum[-1] if cum.shape[0] else jnp.int32(0)
    truncated = jnp.maximum(total - n_slots, 0).astype(jnp.int32)
    slots = jnp.arange(n_slots, dtype=jnp.int32)
    # scatter a marker at each vertex's END offset; prefix-sum counts
    # how many adjacency lists finished at or before each slot.  End
    # offsets past n_slots drop out, so slots inside an overflowing
    # hub's range keep that hub as owner: the clamp keeps the edge
    # prefix, deterministically.
    markers = (jnp.zeros((n_slots,), jnp.int32)
               .at[cum].add(1, mode="drop"))
    owner = jnp.cumsum(markers, dtype=jnp.int32)
    owner_c = jnp.clip(owner, 0, frontier_list.shape[0] - 1)
    prev = jnp.where(owner_c > 0, cum[jnp.maximum(owner_c - 1, 0)], 0)
    u = frontier_list[owner_c]
    valid = slots < total
    u_safe = jnp.where(valid, u, 0)
    e_idx = csr_colstarts[u_safe] + (slots - prev)
    e_idx = jnp.clip(e_idx, 0, csr_rows.shape[0] - 1)
    v = csr_rows[e_idx]
    return u.astype(jnp.int32), v, valid, truncated


def edge_stream(colstarts, rows, frontier_words, list_size: int,
                n_vertices: int, n_slots: int, packed: bool = False):
    """The engine's gather phase: bitmap -> apportioned
    (u, v, valid, truncated) — the *materialized* pipeline's stream.

    ``packed=True`` compacts the bitmap with the SIMD rank-and-scatter
    kernel (kernels/compact.py — the paper's §4 vectorized queue
    generation) instead of the dense ``unpack_bool`` + ``nonzero``
    round trip; the resulting queue is identical (ascending ids,
    sentinel-padded), so the streams are bit-for-bit equal.
    """
    if packed:
        frontier_list, _ = ops.frontier_compact(
            frontier_words, size=list_size, fill=n_vertices)
    else:
        frontier_list = bm.compact(frontier_words, list_size, n_vertices)
    return apportion(colstarts, rows, frontier_list, n_vertices, n_slots)


def rowsweep_stream(colstarts, rows, active_words, n_vertices: int):
    """(u, v, valid) in **rows order** — the jnp form of the fused
    in-kernel gather (kernels/gather_expand.py) and its oracle.

    Owners come from a degree-expansion of ``colstarts`` and the
    frontier gate is a bitmap test per edge (`owner_gate`) — one pass
    over ``rows`` with no compaction, no marker scatter and no
    prefix-sum intermediates (the apportionment machinery the fused
    pipeline removes).
    """
    u = edge_owners(colstarts, int(rows.shape[0]), n_vertices)
    return u, rows, owner_gate(u, rows, active_words, n_vertices)


def owner_gate(owners, rows, active_words, nbr_limit: int):
    """The per-slot gate of a rows-order stream: the slot's owner is in
    ``active_words`` and its neighbor is a real vertex (``< nbr_limit``;
    padding slots hold the sentinel).  The distributed per-chip step
    passes LOCAL owner ids (< v_loc) and GLOBAL neighbors, so there
    ``nbr_limit`` is the global vertex count."""
    return bm.test_bits(active_words, owners) & (rows < nbr_limit)


def edge_owners(colstarts, e_pad: int, n_vertices: int):
    """(E_pad,) owner vertex of every CSR ``rows`` slot — the degree
    expansion of ``colstarts``.  Padding slots carry sentinel
    neighbors, so a neighbor test alone invalidates them regardless of
    the repeat's tail fill."""
    deg = colstarts[1:] - colstarts[:-1]
    return jnp.repeat(jnp.arange(n_vertices, dtype=jnp.int32), deg,
                      total_repeat_length=e_pad)


def compact_worklist(active, n: int):
    """Bool mask (n,) -> (worklist (n,) int32, n_active int32).

    The single home of the scalar-prefetch work-list contract every
    active-scheduled kernel assumes: active indices first, and every
    entry past ``n_active`` clamped to the LAST active index — the
    kernel's index map then feeds Mosaic an unchanged block index,
    which elides the repeated DMA (the sentinel-block trick that
    makes inactive tiles free; a ``pl.when`` guard skips their
    compute).  Shared by `plan_active_tiles` (CSR rows-blocks) and
    `formats.sell.SellFormat._plan_slab_steps` (slab groups).
    """
    n_active = active.sum(dtype=jnp.int32)
    (wl,) = jnp.nonzero(active, size=n, fill_value=0)
    wl = wl.astype(jnp.int32)
    last = wl[jnp.clip(n_active - 1, 0, n - 1)]
    wl = jnp.where(jnp.arange(n) < n_active, wl, last)
    return wl, n_active


def _mark_blocks(start, end, has, tile: int, n_blocks: int):
    """Range-mark + compact: the single home of the block-marking
    algorithm (+1/-1 difference scatter with drop sentinel, prefix
    sum, `compact_worklist`) shared by the queue-based (packed) and
    dense-mask planning arms — they differ only in how the active
    (start, end) adjacency ranges are produced."""
    blk_lo = start // tile
    blk_hi = (end - 1) // tile
    drop = n_blocks + 1
    diff = jnp.zeros((n_blocks + 1,), jnp.int32)
    diff = diff.at[jnp.where(has, blk_lo, drop)].add(1, mode="drop")
    diff = diff.at[jnp.where(has, blk_hi + 1, drop)].add(-1, mode="drop")
    covered = jnp.cumsum(diff)[:n_blocks] > 0
    return compact_worklist(covered, n_blocks)


def mark_blocks_from_queue(colstarts, queue, n_vertices: int, tile: int,
                           n_blocks: int):
    """Range-mark the rows-blocks a compacted vertex queue's adjacency
    touches.  The queue is sentinel-padded (id >= n_vertices => empty
    slot)."""
    is_real = queue < n_vertices
    safe = jnp.where(is_real, queue, 0)
    start = colstarts[safe]
    end = colstarts[safe + 1]
    return _mark_blocks(start, end, is_real & (end > start), tile,
                        n_blocks)


def plan_active_tiles(colstarts, active_words, n_vertices: int,
                      tile: int, n_blocks: int, packed: bool = False):
    """The fused pipeline's per-layer scheduling pass (one root).

    Marks every ``tile``-sized block of ``rows`` that intersects an
    active vertex's adjacency (range-mark via a +1/-1 difference
    scatter + prefix sum — no E-sized arrays) and compacts the marks
    into a `compact_worklist`.  Returns (worklist (n_blocks,) int32,
    n_active int32).

    ``packed=False`` (legacy) expands the bitmap to a dense V-mask and
    range-marks from it; ``packed=True`` compacts the bitmap with the
    SIMD kernel first (V/8 bytes of mask reads + a queue of the live
    vertices) and range-marks from the queue — the packed engine's
    planning arm.  Oversized working sets take the dense arm
    (`ops.compact_fits`), so huge graphs keep traversing like they
    did before the packed default — and since ISSUE 8 the fallback
    emits a ``serve.degrade.vmem_fallback`` `DegradeEvent` instead of
    happening silently.
    """
    v_pad = active_words.shape[0] * bm.BITS_PER_WORD
    if packed:
        if ops.compact_fits(1, v_pad):
            queue, _ = ops.frontier_compact(active_words, size=v_pad,
                                            fill=n_vertices)
            return mark_blocks_from_queue(colstarts, queue, n_vertices,
                                          tile, n_blocks)
        _record_degrade(
            "vmem_fallback",
            reason=ops.budget_detail(
                f"frontier_compact(1x{v_pad})",
                ops.compact_budget(1, v_pad)),
            fallback="dense planner (plan_active_tiles, packed arm "
                     "disabled)")
    dense = bm.unpack_bool(active_words)[:n_vertices]
    start, end = colstarts[:-1], colstarts[1:]
    return _mark_blocks(start, end, dense & (end > start), tile,
                        n_blocks)


def plan_active_tiles_batched(colstarts, active_words, n_vertices: int,
                              tile: int, n_blocks: int,
                              packed: bool = True):
    """Batched planning: (B, W) active bitmaps -> ((B, n_blocks)
    work-lists, (B,) live counts).  The packed arm runs ONE batched
    compaction launch then vmaps the pure-jnp block marking; the
    legacy arm (and any batch x V_pad working set past the compaction
    kernel's VMEM budget) vmaps the dense planner."""
    n_batch, w = active_words.shape
    v_pad = w * bm.BITS_PER_WORD
    if packed:
        if ops.compact_fits(n_batch, v_pad):
            queues, _ = ops.frontier_compact_batched(
                active_words, size=v_pad, fill=n_vertices)
            return jax.vmap(
                lambda q: mark_blocks_from_queue(colstarts, q,
                                                 n_vertices, tile,
                                                 n_blocks))(queues)
        _record_degrade(
            "vmem_fallback",
            reason=ops.budget_detail(
                f"frontier_compact({n_batch}x{v_pad})",
                ops.compact_budget(n_batch, v_pad)),
            fallback="dense planner (plan_active_tiles_batched, "
                     "packed arm disabled)")
    return jax.vmap(
        lambda a: plan_active_tiles(colstarts, a, n_vertices, tile,
                                    n_blocks, packed=False))(
        active_words)


def candidate_scatter(u, v, valid, visited, n_vertices: int, v_cap: int):
    """Encode a layer's discoveries as a min-parent candidate array.

    The deterministic merge primitive of the distributed engine step:
    INF (== n_vertices) everywhere, min discovering parent where a
    valid undiscovered candidate exists.  ``pmin``/``all_to_all`` of
    these arrays resolves inter-chip duplicates reproducibly.
    """
    undiscovered = ~bm.test_bits(visited, v)
    mask = valid & undiscovered & (v < n_vertices)
    idx = jnp.where(mask, v, v_cap)
    cand = jnp.full((v_cap,), n_vertices, jnp.int32)
    return cand.at[idx].min(u, mode="drop")


@jax.jit
def row_popcounts(words):
    """Set-bit count over the trailing word axis: (B, W) -> (B,) or
    (W,) -> scalar.  The one popcount used by loop conditions, depth
    tracking, and the serve engine's finished-slot scan."""
    return jax.lax.population_count(words).astype(jnp.int32).sum(axis=-1)


def masked_edge_sum(dense, deg):
    """Σ deg over True lanes of a dense vertex mask (trailing V axis) —
    the Table 1 'Edges' counter (int32; E < 2^31 is a framework
    invariant asserted at CSR build)."""
    return jnp.where(dense, deg, 0).sum(axis=-1, dtype=jnp.int32)


def _next_pow2(n: int, lo: int = 128) -> int:
    n = max(int(n), lo)
    return 1 << (n - 1).bit_length()


def _auto_tile(e_size: int, interpret: bool) -> int:
    """The CSR edge-stream tile rule.

    Tile selection is owned by the graph *format* (the layout fixes
    the aligned unit — §4.2): `formats.CsrFormat.resolve_tile`
    delegates here, SELL fixes its slab geometry instead.  This
    module-level home survives for `traverse_hostloop`, whose
    ``tile=`` argument drives the A/B prefetch-distance sweeps.
    """
    if not interpret:
        return 1024
    # interpret mode unrolls the grid at trace time: keep it short
    return max(1024, e_size // 32)


_TILE_ENV = "REPRO_BFS_TILE"


def default_tile_csr(fmt=None) -> int:
    """The auto tile through the shared affinity mechanism
    (`formats.affinity.resolve` — ISSUE 6 generalized this PR-4
    one-off into the lookup every auto knob reads).  Priority:
    ``REPRO_BFS_TILE`` env override > the geometry-keyed committed
    row (when ``fmt`` is given) > the PR-4 flat ``affinity.tile<N>``
    rows > the legacy 1024 heuristic."""
    from repro.formats import affinity
    return int(affinity.resolve(fmt, "tile", 1024))


def _resolve_tile_csr(tile: int | None, e_pad: int, fmt=None) -> int:
    """The CSR tile rule (`formats.CsrFormat.resolve_tile`).

    The tile is the fused pipeline's DMA unit AND its prefetch
    distance (§4's knob); it bottoms out at 128 (one lane set) so
    small graphs still resolve to several blocks and the active-tile
    schedule has something to skip.  The auto choice comes from
    `default_tile_csr` (env override > the geometry-keyed BENCH
    affinity row for ``fmt`` > the flat sweep rows > 1024), capped at
    ``e_pad/8`` so small graphs keep >= 8 blocks to skip.  The
    interpret-mode floor keeps the unrolled grid <=32 steps, same
    budget as `_auto_tile`.
    """
    interpret = interpret_mode()
    floor = max(128, e_pad // 32) if interpret else 128
    if tile is None:
        # auto tiles (table or env) never exceed the edge stream —
        # _pad_rows_to_tile pads rows UP to a tile multiple, so an
        # oversized tile would balloon the padded stream itself
        tile = max(128, min(default_tile_csr(fmt), max(e_pad // 8, 128)))
        tile = min(tile, max(e_pad, 128))
    return max(int(tile), floor)


# ---------------------------------------------------------------------------
# The three expansion flavours (batched: leading root axis on state)
# ---------------------------------------------------------------------------

def expand_candidates(u, v, valid, frontier, visited, parent,
                      n_vertices: int, algorithm: str, semiring=None,
                      vals=None):
    """The post-gather Algorithm 2/3 body on any layout's edge stream.

    The single home of the test-mask-scatter(-restore) sequence:
    ``(u, v, valid)`` is a gathered candidate stream — CSR's
    apportioned `edge_stream`, SELL's flattened slab sweep — and the
    body is layout-independent.  Returns (out, visited, parent).

    Passing a `repro.algorithms.semiring.Semiring` (with its ``vals``
    row) switches the body to the generic relaxation — the pure-jnp
    reference of the scatter-min kernels: fold each frontier edge's
    ``vals[u] ⊗ w`` candidate with ⊕ (= min, commutative: no race, no
    restoration), then resolve min-id parents against the finalized
    values.  Returns ``(improved_words, new_vals, parent)`` — the
    frontier-generation triple of `algorithms.traversal`.

    Algorithm 3 (``"simd"``) reads one visited word per slot and
    scatters ``u - V`` into ``P`` where the neighbor is undiscovered;
    the negative marks then give the new frontier (packed), the new
    visited bitmap (``visited | out``) and the restored ``P``.  So per
    slot: one gather, one scatter, beside the caller's gate.  The body
    relies on ``frontier ⊆ visited`` on entry, so it does not test the
    frontier again: `init_root_state` sets the root in both bitmaps,
    and every step returns an ``out`` inside its ``visited``.
    """
    v_pad = parent.shape[0]
    if semiring is not None:
        in_front = bm.test_bits(frontier, u)
        mask = valid & in_front & (v < n_vertices)
        u_val = vals[jnp.clip(u, 0, v_pad - 1)]
        cand = semiring.mul(u_val, u, v)
        idx = jnp.where(mask, v, v_pad)
        new_vals = vals.at[idx].min(cand, mode="drop")
        cur = new_vals[jnp.clip(v, 0, v_pad - 1)]
        win = mask & (cand == cur) \
            & semiring.improved(vals[jnp.clip(v, 0, v_pad - 1)], cur)
        p_layer = jnp.full((v_pad,), jnp.iinfo(jnp.int32).max,
                           jnp.int32).at[jnp.where(win, v, v_pad)] \
            .min(u, mode="drop")
        improved = semiring.improved(vals, new_vals)
        parent = jnp.where(improved, p_layer, parent)
        return bm.pack_bool(improved), new_vals, parent
    if algorithm == "nonsimd":         # Algorithm 2: exact dense updates
        vis_dense = bm.unpack_bool(visited)
        mask = valid & ~vis_dense[jnp.clip(v, 0, v_pad - 1)]
        idx = jnp.where(mask, v, v_pad)
        parent = parent.at[idx].set(u, mode="drop")
        out_dense = (jnp.zeros((v_pad,), bool)
                     .at[idx].set(True, mode="drop"))
        out = bm.pack_bool(out_dense)
        return out, visited | out, parent
    # Algorithm 3: negative P marks, then restoration from them alone
    mask = valid & ~bm.test_bits(visited, v)
    idx = jnp.where(mask, v, v_pad)
    parent = parent.at[idx].set(u - n_vertices, mode="drop")
    marked = parent < 0
    out = bm.pack_bool(marked)
    return out, visited | out, jnp.where(marked, parent + n_vertices,
                                         parent)


def scalar_expand(colstarts, rows, n_vertices: int, frontier, visited,
                  parent, f_size: int, e_size: int, algorithm: str):
    """One plain-jnp top-down CSR layer (Algorithm 2/3): apportioned
    gather + the shared `expand_candidates` body.  The hostloop driver
    and ``bfs_parallel.expand_*`` call this (single root, dense
    compaction — the legacy drivers); the fused engine's batched
    scalar step routes through `_batched_edge_stream` instead.
    Returns (out, visited, parent, truncated)."""
    u, v, valid, truncated = edge_stream(colstarts, rows, frontier,
                                         f_size, n_vertices, e_size)
    out, visited, parent = expand_candidates(
        u, v, valid, frontier, visited, parent, n_vertices, algorithm)
    return out, visited, parent, truncated


def _batched_edge_stream(colstarts, rows, frontier, list_size: int,
                         n_vertices: int, n_slots: int, packed: bool):
    """(B, W) frontier bitmaps -> batched apportioned streams.

    The packed arm compacts the whole batch in one kernel launch and
    vmaps only the pure-jnp apportionment; the legacy arm (and any
    working set past the compaction kernel's VMEM budget, observably —
    ``serve.degrade.vmem_fallback``) vmaps the dense `edge_stream`
    whole."""
    n_batch = frontier.shape[0]
    if packed:
        if ops.compact_fits(n_batch, list_size):
            fl, _ = ops.frontier_compact_batched(
                frontier, size=list_size, fill=n_vertices)
            return jax.vmap(
                lambda l: apportion(colstarts, rows, l, n_vertices,
                                    n_slots))(fl)
        _record_degrade(
            "vmem_fallback",
            reason=ops.budget_detail(
                f"frontier_compact({n_batch}x{list_size})",
                ops.compact_budget(n_batch, list_size)),
            fallback="dense edge_stream (materialized frontier lists, "
                     "packed arm disabled)")
    return jax.vmap(
        lambda f: edge_stream(colstarts, rows, f, list_size, n_vertices,
                              n_slots))(frontier)


def _make_scalar_step(colstarts, rows, n_vertices: int, v_pad: int,
                      e_pad: int, algorithm: str, tile: int,
                      packed: bool = True):
    """Plain-jnp Algorithm 2/3 layer, vmapped over the root axis.

    Always materialized (the apportioned stream IS the scalar
    algorithm); its StepAux reports the full stream's tile count so
    the accounting stays comparable across modes.  Under ``packed``
    the frontier-list build is the SIMD compaction kernel instead of
    the dense unpack/nonzero pass."""
    tiles_per_root = -(-e_pad // tile)

    def step(frontier, visited, parent):
        with ops.count_launches() as c:
            u, v, valid, trunc = _batched_edge_stream(
                colstarts, rows, frontier, v_pad, n_vertices, e_pad,
                packed)
            out, visited, parent = jax.vmap(
                lambda u1, v1, val1, f1, vi1, p1: expand_candidates(
                    u1, v1, val1, f1, vi1, p1, n_vertices, algorithm)
            )(u, v, valid, frontier, visited, parent)
        aux = StepAux(jnp.int32(frontier.shape[0] * tiles_per_root),
                      trunc.sum(dtype=jnp.int32), c.count)
        return out, visited, parent, aux

    return step


def kernel_expand_restore(expand_fn, nbr, cand, valid, frontier,
                          visited, parent, n_vertices: int, tile: int,
                          check_frontier: bool = False):
    """Racy kernel expansion + restoration + delta merge (§3.3.2).

    The single home of the expand -> restore -> OR-delta sequence;
    ``expand_fn`` is `ops.expand` (single root) or `ops.expand_batched`
    (leading root axis).  Returns (out, visited, parent)."""
    out_racy, p_racy = expand_fn(
        nbr, cand, valid.astype(jnp.int32), frontier, visited,
        jnp.zeros_like(frontier), parent, n_vertices=n_vertices,
        tile=tile, check_frontier=check_frontier)
    p_fixed, delta = ops.restore(p_racy, n_vertices=n_vertices)
    return out_racy | delta, visited | delta, p_fixed


def _make_simd_step(colstarts, rows, n_vertices: int, v_pad: int,
                    e_pad: int, tile: int, packed: bool = True):
    """§4 SIMD layer, *materialized* pipeline: apportioned HBM stream
    + batched Pallas expansion + kernel restoration."""
    tiles_per_root = -(-e_pad // tile)

    def step(frontier, visited, parent):
        with ops.count_launches() as c:
            u, v, valid, trunc = _batched_edge_stream(
                colstarts, rows, frontier, v_pad, n_vertices, e_pad,
                packed)
            out, visited, parent = kernel_expand_restore(
                ops.expand_batched, u, v, valid, frontier, visited,
                parent, n_vertices, tile)
        aux = StepAux(jnp.int32(frontier.shape[0] * tiles_per_root),
                      trunc.sum(dtype=jnp.int32), c.count)
        return out, visited, parent, aux

    return step


def _pad_rows_to_tile(rows, n_vertices: int, tile: int):
    """Sentinel-pad the CSR rows to a tile multiple — once, at step
    build time (a loop constant), never inside the layer loop."""
    pad = (-int(rows.shape[0])) % tile
    if pad:
        rows = jnp.concatenate(
            [rows, jnp.full((pad,), n_vertices, jnp.int32)])
    return rows


def _make_fused_step(colstarts, rows_t, n_vertices: int, tile: int,
                     bottom_up: bool, packed: bool = True,
                     prefetch_depth: int = 0):
    """One fused_gather layer (ISSUE 3), both directions.

    Top-down plans the active rows-blocks from the *frontier*'s
    adjacency; bottom-up from the *unvisited* set's (``~visited`` —
    padding is premarked, so the complement is exactly the real
    undiscovered vertices), with the kernel testing each gathered
    neighbor against the frontier bitmap.  Either way: no
    materialized (u, v, valid) round trip.  ``rows_t`` is the
    tile-padded rows array (padded once in `_make_steps`).

    ``packed`` routes the planning pass through the SIMD compaction
    kernel (V/8 mask bytes instead of a dense V-mask);
    ``prefetch_depth`` > 0 switches the gather kernel to its manual
    double-buffered DMA input pipeline (tile N+1 in flight while tile
    N computes — the §4 prefetch-distance knob)."""
    n_blocks = int(rows_t.shape[0]) // tile

    def step(frontier, visited, parent):
        with ops.count_launches() as c:
            active = ~visited if bottom_up else frontier
            wl, na = plan_active_tiles_batched(colstarts, active,
                                               n_vertices, tile,
                                               n_blocks, packed=packed)
            out_racy, p_racy = ops.gather_expand_batched(
                wl, na, rows_t, colstarts, frontier, visited,
                jnp.zeros_like(frontier), parent, n_vertices=n_vertices,
                tile=tile, bottom_up=bottom_up,
                prefetch_depth=prefetch_depth)
            p_fixed, delta = ops.restore(p_racy, n_vertices=n_vertices)
        aux = StepAux(na.sum(dtype=jnp.int32), jnp.int32(0), c.count)
        return out_racy | delta, visited | delta, p_fixed, aux

    return step


def _make_megakernel_step(colstarts, rows_t, n_vertices: int, tile: int,
                          bottom_up: bool, prefetch_depth: int = 0):
    """One whole layer in ONE Pallas call (ISSUE 6): the in-kernel
    plan + compact + gather-expand + restoration megakernel.  The
    work-list never leaves SMEM/VMEM; restoration is inlined at the
    final grid step, so the returned ``out`` is already repaired and
    the visited merge is a plain word OR (``out == delta | out_racy``
    holds because every true discovery carries a negative P mark —
    see kernels/layer_fused.py)."""

    def step(frontier, visited, parent):
        with ops.count_launches() as c:
            out, parent, na = ops.layer_fused_batched(
                rows_t, colstarts, frontier, visited, parent,
                n_vertices=n_vertices, tile=tile, bottom_up=bottom_up,
                prefetch_depth=prefetch_depth)
        aux = StepAux(na.sum(dtype=jnp.int32), jnp.int32(0), c.count)
        return out, visited | out, parent, aux

    return step


def make_xla_steps(src, dst, n_vertices: int, algorithm: str,
                   tiles_per_root: int) -> dict:
    """The per-layer steps of ``pipeline="xla"``: the shared
    `expand_candidates` body, as XLA ops, over a flat (owner, neighbor)
    slot stream.

    The TPU compiler lowers none of the expansion kernels
    (`kernels.TPU_REFUSALS`), so this is the pipeline the streamed
    formats declare in ``tpu_pipelines``.  ``src``/``dst`` are shared
    by every root and sentinel-padded (``dst == V`` on padding slots).
    Top-down gates a slot on its owner being in the frontier and
    discovers the neighbor; bottom-up swaps the stream, gating on the
    neighbor being in the frontier and discovering the owner.  Per
    slot the step gathers two words (the gate's frontier word, the
    discovered side's visited word) and scatters at most one parent
    mark: 2 gathers and 1 scatter over the stream, relying on
    ``frontier ⊆ visited`` on entry (`expand_candidates`).  The
    plain and SIMD top-down modes are one step.  Every layer sweeps
    the whole stream: ``tiles_per_root`` is its tile count for the
    stats.  Roots run one after another (`lax.map`): a vmapped sweep
    puts the root axis minor in its (E, B) gathers, which the TPU pads
    from B to 128 lanes (34 GiB at Graph500 scale 20 with B = 8)."""
    real = dst < n_vertices

    def make_step(bottom_up: bool):
        u, v = (dst, src) if bottom_up else (src, dst)

        def one_root(state):
            frontier, visited, parent = state
            valid = real & bm.test_bits(frontier, u)
            return expand_candidates(u, v, valid, frontier, visited,
                                     parent, n_vertices, algorithm)

        def step(frontier, visited, parent):
            out, visited, parent = jax.lax.map(
                one_root, (frontier, visited, parent))
            aux = StepAux(jnp.int32(frontier.shape[0] * tiles_per_root),
                          jnp.int32(0), jnp.int32(0))
            return out, visited, parent, aux
        return step

    top_down = make_step(bottom_up=False)
    return {MODE_SCALAR: top_down, MODE_SIMD: top_down,
            MODE_BOTTOMUP: make_step(bottom_up=True)}


def _bottomup_stream(colstarts, rows, visited_words, n_vertices: int,
                     c_size: int, e_size: int):
    """Apportion the adjacency of *unvisited* vertices (one root) —
    the hostloop / legacy dense arm; the fused engine's batched
    bottom-up step compacts ``~visited`` with the batched kernel
    instead (padding vertices are premarked visited, so the word
    complement is exactly the real undiscovered set)."""
    unvisited = ~bm.unpack_bool(visited_words)
    (cands,) = jnp.nonzero(unvisited, size=c_size,
                           fill_value=n_vertices)
    return apportion(colstarts, rows, cands.astype(jnp.int32),
                     n_vertices, e_size)


def _make_bottomup_step(colstarts, rows, n_vertices: int, v_pad: int,
                        e_pad: int, tile: int, packed: bool = True):
    """Bottom-up layer, materialized pipeline: apportion the
    *unvisited* adjacency, test each neighbor against the frontier
    bitmap inside the kernel."""
    tiles_per_root = -(-e_pad // tile)

    def step(frontier, visited, parent):
        with ops.count_launches() as ct:
            fits = ops.compact_fits(frontier.shape[0], v_pad)
            if packed and not fits:
                _record_degrade(
                    "vmem_fallback",
                    reason=ops.budget_detail(
                        f"frontier_compact({frontier.shape[0]}x"
                        f"{v_pad})",
                        ops.compact_budget(frontier.shape[0], v_pad)),
                    fallback="dense bottom-up candidate stream "
                             "(packed arm disabled)")
            if packed and fits:
                cands, _ = ops.frontier_compact_batched(
                    ~visited, size=v_pad, fill=n_vertices)
                cand, nbr, valid, trunc = jax.vmap(
                    lambda c: apportion(colstarts, rows, c, n_vertices,
                                        e_pad))(cands)
            else:
                cand, nbr, valid, trunc = jax.vmap(
                    lambda vis: _bottomup_stream(colstarts, rows, vis,
                                                 n_vertices, v_pad,
                                                 e_pad))(visited)
            out, visited, parent = kernel_expand_restore(
                ops.expand_batched, nbr, cand, valid, frontier, visited,
                parent, n_vertices, tile, check_frontier=True)
        aux = StepAux(jnp.int32(frontier.shape[0] * tiles_per_root),
                      trunc.sum(dtype=jnp.int32), ct.count)
        return out, visited, parent, aux

    return step


def check_pipeline(pipeline: str) -> None:
    """Fail loudly on a mistyped pipeline name — every step builder
    routes through this so a typo can't silently select the legacy
    materialized path."""
    if pipeline not in PIPELINES:
        raise ValueError(f"unknown pipeline {pipeline!r}; "
                         f"expected one of {PIPELINES}")


def _make_steps(colstarts, rows, n_vertices, v_pad, e_pad, algorithm,
                tile, pipeline: str = "fused_gather",
                packed: bool = True, prefetch_depth: int = 0):
    check_pipeline(pipeline)
    if pipeline == "xla":
        return make_xla_steps(edge_owners(colstarts, e_pad, n_vertices),
                              rows, n_vertices, algorithm,
                              -(-e_pad // tile))
    # the persistent pipeline's PER-LAYER steps (the serve tier's
    # layer_step tick) are the megakernel steps — whole-traversal
    # queries never reach here (they route through
    # `_traverse_persistent` before steps are built)
    if pipeline in ("megakernel", "persistent"):
        rows_t = _pad_rows_to_tile(rows, n_vertices, tile)
        n_blocks = int(rows_t.shape[0]) // tile
        if ops.megakernel_fits(v_pad // bm.BITS_PER_WORD, v_pad,
                               int(colstarts.shape[0]), tile,
                               prefetch_depth, n_blocks):
            simd = _make_megakernel_step(colstarts, rows_t, n_vertices,
                                         tile, bottom_up=False,
                                         prefetch_depth=prefetch_depth)
            bottomup = _make_megakernel_step(
                colstarts, rows_t, n_vertices, tile, bottom_up=True,
                prefetch_depth=prefetch_depth)
        else:
            # observable degrade, mirroring ops.compact_fits: a
            # working set past the fused VMEM budget traverses via the
            # unfused fused_gather steps (the stats launch counter
            # then honestly reports the unfused cost)
            _record_degrade(
                "vmem_fallback",
                reason=ops.budget_detail(
                    f"megakernel(v_pad={v_pad}, tile={tile}, "
                    f"blocks={n_blocks}, depth={prefetch_depth})",
                    ops.megakernel_budget(
                        v_pad // bm.BITS_PER_WORD, v_pad,
                        int(colstarts.shape[0]), tile, prefetch_depth,
                        n_blocks)),
                fallback="pipeline='fused_gather' unfused steps "
                         "(3 launches/layer instead of 1)")
            simd = _make_fused_step(colstarts, rows_t, n_vertices,
                                    tile, bottom_up=False,
                                    packed=packed,
                                    prefetch_depth=prefetch_depth)
            bottomup = _make_fused_step(colstarts, rows_t, n_vertices,
                                        tile, bottom_up=True,
                                        packed=packed,
                                        prefetch_depth=prefetch_depth)
    elif pipeline == "fused_gather":
        rows_t = _pad_rows_to_tile(rows, n_vertices, tile)
        simd = _make_fused_step(colstarts, rows_t, n_vertices, tile,
                                bottom_up=False, packed=packed,
                                prefetch_depth=prefetch_depth)
        bottomup = _make_fused_step(colstarts, rows_t, n_vertices,
                                    tile, bottom_up=True, packed=packed,
                                    prefetch_depth=prefetch_depth)
    else:
        simd = _make_simd_step(colstarts, rows, n_vertices, v_pad,
                               e_pad, tile, packed=packed)
        bottomup = _make_bottomup_step(colstarts, rows, n_vertices,
                                       v_pad, e_pad, tile,
                                       packed=packed)
    return {
        MODE_SCALAR: _make_scalar_step(colstarts, rows, n_vertices,
                                       v_pad, e_pad, algorithm, tile,
                                       packed=packed),
        MODE_SIMD: simd,
        MODE_BOTTOMUP: bottomup,
    }


# ---------------------------------------------------------------------------
# The fused driver: whole search (all layers, all roots) in one launch
# ---------------------------------------------------------------------------

def init_root_state(root, base_visited, n_vertices: int):
    """Frontier/visited/parent arrays for one fresh root.

    ``base_visited`` is the padding-premarked visited bitmap
    (`csr.init_visited`).  The single init convention shared by the
    fused engine and the serve engine's slot refill."""
    v_pad = base_visited.shape[0] * bm.BITS_PER_WORD
    frontier = bm.set_bits_exact(bm.zeros(v_pad), root)
    visited = bm.set_bits_exact(base_visited, root)
    parent = jnp.full((v_pad,), n_vertices, jnp.int32).at[root].set(root)
    return frontier, visited, parent


def _init_batched(roots, n_vertices: int, v_pad: int):
    base_vis = padding_premarked_visited(n_vertices)
    return jax.vmap(
        lambda r: init_root_state(r, base_vis, n_vertices)
    )(roots.astype(jnp.int32))


def _traverse_persistent(fmt, roots, spec) -> EngineResult:
    """The ISSUE 9 whole-traversal driver: init the batch state, hand
    it to the format's persistent kernel (ONE Pallas launch — layer
    loop, §4.1 direction decision and termination all in-kernel,
    state VMEM-resident across layers) and repackage its
    ``(frontier, visited, parent, depths, layers, stats)`` contract
    as an `EngineResult`.  The stats launch column charges 1 per
    *traversal* (at the layer-0 row), vs the megakernel's 1/layer."""
    frontier, visited, parent = _init_batched(roots, fmt.n_vertices,
                                              fmt.n_vertices_padded)
    frontier, visited, parent, depths, layers, stats = \
        fmt.persistent_run(frontier, visited, parent, spec)
    return EngineResult(BfsState(frontier, visited, parent, layers[0]),
                        depths, stats)


def _traverse_impl(fmt, roots, spec) -> EngineResult:
    """The fused engine body, generic over a `formats.GraphFormat`.

    ``spec`` is a *resolved* `repro.api.spec.TraversalSpec` — the one
    configuration object every knob now lives on (policy, algorithm,
    pipeline, packed, tile, prefetch_depth, max_layers).  Every
    per-layer step (scalar / SIMD kernel / bottom-up) is built by the
    *format* (``fmt.make_steps(spec)``) — the layout owns its gather
    primitive and its ``pipeline`` flavour — while the
    measure/decide/restore pipeline and the single ``lax.while_loop``
    stay layout-independent.  ``roots`` is a (B,) int32 array; every
    state array carries the leading root axis.  No host
    synchronization between layers.

    ``spec.packed=True`` (the native representation since ISSUE 4)
    keeps the whole per-layer pipeline on packed uint32 words:
    workload counters come from word popcounts and the word-aligned
    degree matrix, planning/compaction run the SIMD rank-and-scatter
    kernel — per-layer mask traffic is V/8 bytes instead of the
    4V-byte dense masks the ``packed=False`` (legacy parity) arm
    materializes.
    """
    if spec.pipeline == "persistent":
        # trace-time VMEM admission: the persistent kernel pins the
        # WHOLE batch's state across layers, so the budget scales
        # with the root batch — past it, degrade observably to the
        # megakernel per-layer path (1 launch/layer), which has its
        # own further degrade to the unfused steps in `_make_steps`
        if fmt.persistent_fits(int(roots.shape[0]), spec):
            return _traverse_persistent(fmt, roots, spec)
        fallback = ("megakernel" if fmt.supports_megakernel
                    else "fused_gather")
        _record_degrade(
            "vmem_fallback",
            reason=(f"persistent(v_pad={fmt.n_vertices_padded}, "
                    f"roots={int(roots.shape[0])}, tile={spec.tile}, "
                    f"max_layers={spec.max_layers}, "
                    f"depth={spec.prefetch_depth}) whole-batch "
                    f"working set exceeds the VMEM budget"),
            fallback=f"pipeline={fallback!r} per-layer steps "
                     f"(>=1 launch/layer instead of 1/traversal)")
        spec = spec.replace(pipeline=fallback)

    policy = spec.policy
    packed = spec.packed
    max_layers = spec.max_layers
    n_vertices = fmt.n_vertices
    v_pad = fmt.n_vertices_padded
    deg = fmt.degrees()
    deg_mat = bm.degree_matrix(deg, v_pad)     # loop constant
    steps = fmt.make_steps(spec)
    modes = tuple(policy.modes)

    def rows_workload(words):          # (B, W) -> per-root counters
        if packed:
            edges = jax.vmap(
                lambda w: bm.masked_degree_sum(w, deg_mat))(words)
            return row_popcounts(words), edges
        dense = jax.vmap(bm.unpack_bool)(words)[:, :n_vertices]
        return row_popcounts(words), masked_edge_sum(dense, deg)

    frontier, visited, parent = _init_batched(roots, n_vertices, v_pad)
    n_roots = roots.shape[0]
    carry0 = (frontier, visited, parent, jnp.int32(0), jnp.asarray(False),
              jnp.zeros((n_roots,), jnp.int32),
              jnp.zeros((max_layers, _N_ST), jnp.int32))

    def cond(s):
        frontier, layer = s[0], s[3]
        return (row_popcounts(frontier).sum() > 0) & (layer < max_layers)

    def body(s):
        frontier, visited, parent, layer, bottom_up, depths, stats = s
        # named scopes mark the engine phases in XLA profiles
        # (obs.trace.xla_profiler / TensorBoard) — trace-time only
        with jax.named_scope("bfs.measure_decide"):
            f_count_b, f_edges_b = rows_workload(frontier)
            # policy counters aggregate in float32: per-root values are
            # int32-safe, the batch sum may not be (see Workload
            # docstring)
            if policy.needs_unvisited and packed:
                # padding is premarked visited, so the word complement
                # IS the real undiscovered set — no dense mask round
                # trip
                u_words = ~visited
                u_count = row_popcounts(u_words).sum() \
                    .astype(jnp.float32)
                u_edges = jax.vmap(
                    lambda w: bm.masked_degree_sum(w, deg_mat))(u_words) \
                    .astype(jnp.float32).sum()
            elif policy.needs_unvisited:
                u_dense = ~jax.vmap(
                    bm.unpack_bool)(visited)[:, :n_vertices]
                u_count = u_dense.sum(dtype=jnp.float32)
                u_edges = masked_edge_sum(u_dense, deg) \
                    .astype(jnp.float32).sum()
            else:
                u_count = u_edges = jnp.float32(0)
            w = Workload(layer, f_count_b.astype(jnp.float32).sum(),
                         f_edges_b.astype(jnp.float32).sum(), u_count,
                         u_edges, n_vertices, bottom_up,
                         n_roots=roots.shape[0])
            mode, bottom_up = policy.decide(w)

        with jax.named_scope("bfs.expand"):
            if len({id(steps[m]) for m in modes}) == 1:
                # one distinct step (single-mode policy, or a format
                # that maps every mode onto one sweep): call directly
                # instead of tracing the same body once per switch
                # branch
                new_f, visited, parent, aux = steps[modes[0]](
                    frontier, visited, parent)
            else:
                branch = sum(jnp.where(mode == m, jnp.int32(i), 0)
                             for i, m in enumerate(modes))
                new_f, visited, parent, aux = jax.lax.switch(
                    branch,
                    [functools.partial(lambda fn, op: fn(*op), steps[m])
                     for m in modes],
                    (frontier, visited, parent))
        with jax.named_scope("bfs.stats"):
            discovered = row_popcounts(new_f).sum()
            # stats stay int32 (exact Table 1 counters; single-root
            # always fits, extreme batched sums may clip — diagnostics
            # only)
            stats = stats.at[layer].set(
                jnp.stack([f_count_b.sum(), f_edges_b.sum(), discovered,
                           mode, jnp.int32(1), aux.tiles, aux.truncated,
                           jnp.asarray(aux.launches, jnp.int32)]))
            depths = depths + (f_count_b > 0).astype(jnp.int32)
        return (new_f, visited, parent, layer + 1, bottom_up, depths,
                stats)

    frontier, visited, parent, layer, _, depths, stats = \
        jax.lax.while_loop(cond, body, carry0)
    return EngineResult(BfsState(frontier, visited, parent, layer),
                        depths, stats)


_UNSET = object()       # legacy-shim sentinel: "knob not passed"

_KNOB_DEFAULTS = dict(policy=None, algorithm="simd", tile=None,
                      max_layers=64, pipeline=None, packed=True,
                      prefetch_depth=0)


def _spec_from_knobs(entry: str, spec, knobs: dict):
    """The legacy shims' single spec builder.

    ``knobs`` maps knob name -> value-or-_UNSET.  Explicit loose knobs
    emit the DeprecationWarning (the spec is the supported surface);
    mixing ``spec=`` with loose knobs is an error.  Returns an
    *unresolved* spec — resolution happens once, in `api.plan.plan`.
    """
    explicit = {k: v for k, v in knobs.items() if v is not _UNSET}
    if spec is not None:
        if explicit:
            raise ValueError(
                f"{entry}: pass either spec= or the loose knobs "
                f"({sorted(explicit)}), not both")
        return spec
    if explicit:
        warnings.warn(
            f"{entry}: the loose-knob form "
            f"({', '.join(sorted(explicit))}) is deprecated; pass "
            f"spec=repro.bfs.TraversalSpec(...) instead",
            DeprecationWarning, stacklevel=3)
    return make_spec(**{**_KNOB_DEFAULTS, **explicit})


def make_spec(*, policy=None, algorithm: str = "simd",
              tile: int | None = None, max_layers: int = 64,
              pipeline: str | None = None, packed: bool = True,
              prefetch_depth: int = 0):
    """Build a `TraversalSpec` from legacy-style knob values — the ONE
    knob->spec constructor (``policy=None`` -> `TopDown()`,
    ``tile=None`` -> the format's auto rule, ``pipeline=None`` ->
    ``"fused_gather"``, or ``"auto"`` on a TPU, whose compiler refuses
    that pipeline's kernels).  Shared by the deprecated shims (via
    `_spec_from_knobs`) and the `run_bfs*` wrapper drivers, so the
    legacy default mapping cannot drift between surfaces."""
    from repro.api.spec import TraversalSpec
    if pipeline is None:
        pipeline = "fused_gather" if interpret_mode() else "auto"
    return TraversalSpec(
        policy=policy if policy is not None else TopDown(),
        algorithm=algorithm,
        pipeline=pipeline,
        packed=packed,
        tile="auto" if tile is None else tile,
        prefetch_depth=prefetch_depth,
        max_layers=max_layers)


def traverse_arrays(colstarts, rows, roots, *, n_vertices: int,
                    policy=_UNSET, algorithm=_UNSET, tile=_UNSET,
                    max_layers=_UNSET, pipeline=_UNSET, packed=_UNSET,
                    prefetch_depth=_UNSET, spec=None) -> EngineResult:
    """The fused engine on raw CSR arrays (shard_map/dry-run friendly).

    Kept as the array-level entry for callers that only hold arrays,
    not a `Csr` (distributed per-chip programs, ``.lower()`` dry
    runs).  A thin shim over `repro.api.plan` since ISSUE 5: the
    arrays are viewed through `CsrFormat` and the loose knobs
    (deprecated — pass ``spec=``) become a `TraversalSpec`, so this
    entry shares the plan cache's one executable per (geometry,
    resolved spec).  ``tile`` now defaults to the format's auto choice
    (the committed BENCH affinity sweep), not a hardwired 1024 — the
    resolved spec is the single source of truth.
    """
    from repro.api.plan import plan as _plan
    from repro.formats.csr_format import CsrFormat
    fmt = CsrFormat(colstarts, rows, n_vertices, int(rows.shape[0]))
    s = _spec_from_knobs(
        "traverse_arrays", spec,
        dict(policy=policy, algorithm=algorithm, tile=tile,
             max_layers=max_layers, pipeline=pipeline, packed=packed,
             prefetch_depth=prefetch_depth))
    return _plan(fmt, s).run_batched(roots)


def traverse_format(fmt, roots, *, policy=_UNSET, algorithm=_UNSET,
                    tile=_UNSET, max_layers=_UNSET, pipeline=_UNSET,
                    packed=_UNSET, prefetch_depth=_UNSET,
                    spec=None) -> EngineResult:
    """The fused engine on any registered `GraphFormat` pytree.

    A thin shim over `repro.api.plan` since ISSUE 5 (one compile per
    (format class, geometry, resolved spec)).  ``tile`` now defaults
    to the *format's* auto choice — the old ``tile=1`` default
    silently degraded callers that bypassed `fmt.resolve_tile`; the
    resolved spec is the single source of truth.
    """
    from repro.api.plan import plan as _plan
    s = _spec_from_knobs(
        "traverse_format", spec,
        dict(policy=policy, algorithm=algorithm, tile=tile,
             max_layers=max_layers, pipeline=pipeline, packed=packed,
             prefetch_depth=prefetch_depth))
    return _plan(fmt, s).run_batched(roots)


def traverse(graph, roots, *, policy=_UNSET, algorithm=_UNSET,
             tile=_UNSET, max_layers=_UNSET, pipeline=_UNSET,
             packed=_UNSET, prefetch_depth=_UNSET,
             spec=None) -> EngineResult:
    """Run the fused engine for one root or a batch of roots.

    A thin shim over `repro.api.plan`/`repro.bfs` since ISSUE 5: all
    knobs live on ONE `TraversalSpec` (pass ``spec=``; the loose
    keyword form below is deprecated but preserved), resolved once and
    compiled once per (format class, geometry, resolved spec).

    Args:
      graph: a `Csr` (traversed via `CsrFormat`) or any built
        `formats.GraphFormat` (SELL-C-σ, bitmap-compressed, ...).
      roots: an int (single-root — result arrays are unbatched) or a
        sequence of ints (multi-root in one launch; every result array
        gains a leading root axis).
      spec: a `repro.bfs.TraversalSpec`; its fields are the one home
        of the former loose knobs (policy, algorithm, pipeline,
        packed, tile, prefetch_depth, max_layers — see the spec's
        docstring for the field -> paper-knob map).
      policy/algorithm/tile/max_layers/pipeline/packed/prefetch_depth:
        deprecated loose-knob form; same semantics as the spec fields
        (policy=None -> TopDown(), tile=None -> the format's auto
        choice).

    In batched mode the policy decides ONCE per layer from the
    batch-summed counters (one mode for the whole batch keeps the loop
    single-branch); finished roots flow through as no-ops.
    """
    from repro.api.plan import plan as _plan
    s = _spec_from_knobs(
        "traverse", spec,
        dict(policy=policy, algorithm=algorithm, tile=tile,
             max_layers=max_layers, pipeline=pipeline, packed=packed,
             prefetch_depth=prefetch_depth))
    return _plan(graph, s).run(roots)


def layer_stats(result: EngineResult) -> list[LayerStats]:
    """Decode the on-device stats buffer (one transfer, post-loop)."""
    buf = np.asarray(result.stats)
    out = []
    for i in range(buf.shape[0]):
        if not buf[i, _ST_ACTIVE]:
            break
        out.append(LayerStats(
            layer=i,
            frontier_vertices=int(buf[i, _ST_FRONTIER]),
            edges_examined=int(buf[i, _ST_EDGES]),
            discovered=int(buf[i, _ST_DISCOVERED]),
            active_tiles=int(buf[i, _ST_TILES]),
            truncated_edges=int(buf[i, _ST_TRUNC]),
            launches=int(buf[i, _ST_LAUNCH])))
    return out


def direction_log(result: EngineResult) -> list[str]:
    """Per-layer direction strings ("topdown"/"bottomup") from stats."""
    buf = np.asarray(result.stats)
    return [MODE_NAMES[int(buf[i, _ST_MODE])]
            for i in range(buf.shape[0]) if buf[i, _ST_ACTIVE]]


# ---------------------------------------------------------------------------
# One batched layer tick (the serve engine's step function)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_vertices", "algorithm"))
def layer_step(colstarts, rows, frontier, visited, parent, *,
               n_vertices: int, algorithm: str = "simd"):
    """Advance every root in the batch by exactly one layer (raw CSR
    arrays).

    The array-level counterpart of `layer_step_format` — which is what
    `serve.graph_engine.GraphEngine` ticks through since the format
    subsystem landed; this entry remains for callers that only hold
    ``colstarts/rows``.  Slots with an empty frontier flow through as
    no-ops (their edge stream is all sentinel).
    """
    v_pad = parent.shape[-1]
    e_pad = int(rows.shape[0])
    step = _make_scalar_step(colstarts, rows, n_vertices, v_pad, e_pad,
                             algorithm, _resolve_tile_csr(None, e_pad))
    return step(frontier, visited, parent)[:3]


def layer_step_format(fmt, frontier, visited, parent, *,
                      algorithm=_UNSET, pipeline=_UNSET, packed=_UNSET,
                      prefetch_depth=_UNSET, spec=None):
    """Format-generic one-layer tick (the serve engine's step).

    Same contract as `layer_step`, but the per-layer step comes from
    the graph format (`fmt.make_steps(spec)`) — the serve layer picks
    the layout per graph at load time and ticks through it.  A thin
    shim over the plan cache's single-layer executable since ISSUE 5
    (`serve.graph_engine.GraphEngine` holds its `CompiledTraversal`
    directly and skips this shim).  Since ISSUE 3 the
    ``algorithm="simd"`` tick routes through the format's SIMD step —
    for CSR that is the fused in-kernel gather, so a serve batch full
    of thin frontiers (or drained slots, n_active == 0) costs tiles
    proportional to the live work instead of E_pad/tile.  Serve batch
    shapes never change, so this compiles once per (format geometry,
    resolved spec, batch shape).
    """
    from repro.api.plan import plan as _plan
    s = _spec_from_knobs(
        "layer_step_format", spec,
        dict(algorithm=algorithm, pipeline=pipeline, packed=packed,
             prefetch_depth=prefetch_depth))
    return _plan(fmt, s).layer_step(frontier, visited, parent)


# ---------------------------------------------------------------------------
# Legacy host-loop driver (pow2 buckets; for A/B and workload studies)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2,))
def _layer_workload(frontier, colstarts, n_vertices):
    """Concrete (|frontier|, Σdeg) for bucket selection."""
    dense = bm.unpack_bool(frontier)[:n_vertices]
    deg = colstarts[1:] - colstarts[:-1]
    return row_popcounts(frontier), masked_edge_sum(dense, deg)


@functools.partial(jax.jit, static_argnums=(2,))
def _unvisited_workload(visited, colstarts, n_vertices):
    dense = ~bm.unpack_bool(visited)[:n_vertices]
    deg = colstarts[1:] - colstarts[:-1]
    return dense.sum(dtype=jnp.int32), masked_edge_sum(dense, deg)


@functools.partial(jax.jit,
                   static_argnames=("n_vertices", "mode", "algorithm",
                                    "f_size", "e_size", "tile"))
def _hostloop_layer(colstarts, rows, frontier, visited, parent, *,
                    n_vertices, mode, algorithm, f_size, e_size, tile):
    """One bucketed layer at exact pow2 shapes, any mode.

    Always the materialized pipeline (the hostloop is the legacy A/B
    driver); returns (out, visited, parent, truncated)."""
    if mode == MODE_SCALAR:
        return scalar_expand(colstarts, rows, n_vertices, frontier,
                             visited, parent, f_size, e_size, algorithm)
    if mode == MODE_SIMD:
        u, v, valid, trunc = edge_stream(colstarts, rows, frontier,
                                         f_size, n_vertices, e_size)
        return kernel_expand_restore(ops.expand, u, v, valid, frontier,
                                     visited, parent, n_vertices,
                                     tile) + (trunc,)
    # MODE_BOTTOMUP: f_size buckets the unvisited-candidate list
    cand, nbr, valid, trunc = _bottomup_stream(colstarts, rows, visited,
                                               n_vertices, f_size,
                                               e_size)
    return kernel_expand_restore(ops.expand, nbr, cand, valid, frontier,
                                 visited, parent, n_vertices, tile,
                                 check_frontier=True) + (trunc,)


def traverse_hostloop(csr: Csr, root: int, *, policy=None,
                      algorithm: str = "simd", tile: int | None = None,
                      max_layers: int = 1024,
                      collect_stats: bool = False):
    """Python layer-loop driver with power-of-two shape buckets.

    Exact work per layer (the paper's Table 1 workload), at the cost of
    one ``int(count)`` device sync and a possible recompile per new
    bucket pair.  The measured A/B counterpart of `traverse`.
    Returns (state, stats, direction_log).
    """
    policy = policy if policy is not None else TopDown()
    interpret = interpret_mode()
    v_pad = csr.n_vertices_padded
    frontier = bm.set_bits_exact(bm.zeros(v_pad),
                                 jnp.asarray([root], jnp.int32))
    visited = bm.set_bits_racy(init_visited(csr),
                               jnp.asarray([root], jnp.int32))
    parent = jnp.full((v_pad,), csr.n_vertices, jnp.int32) \
        .at[root].set(root)
    bottom_up = jnp.asarray(False)
    stats: list[LayerStats] = []
    log: list[str] = []
    layer = 0
    for _ in range(max_layers):
        count, edges = _layer_workload(frontier, csr.colstarts,
                                       csr.n_vertices)
        count, edges = int(count), int(edges)
        if count == 0:
            break
        if policy.needs_unvisited:
            u_count, u_edges = _unvisited_workload(visited, csr.colstarts,
                                                   csr.n_vertices)
            u_count, u_edges = int(u_count), int(u_edges)
        else:
            u_count = u_edges = 0
        w = Workload(jnp.int32(layer), jnp.int32(count), jnp.int32(edges),
                     jnp.int32(u_count), jnp.int32(u_edges),
                     csr.n_vertices, bottom_up)
        mode_t, bottom_up = policy.decide(w)
        mode = int(mode_t)
        if mode == MODE_BOTTOMUP:
            f_size = _next_pow2(u_count)
            e_size = _next_pow2(max(u_edges, 1))
        else:
            f_size = _next_pow2(count)
            e_size = _next_pow2(max(edges, 1))
        t = tile if tile is not None else _auto_tile(e_size, interpret)
        frontier, visited, parent, trunc = _hostloop_layer(
            csr.colstarts, csr.rows, frontier, visited, parent,
            n_vertices=csr.n_vertices, mode=mode, algorithm=algorithm,
            f_size=f_size, e_size=e_size, tile=t)
        log.append(MODE_NAMES[mode])
        if collect_stats:
            stats.append(LayerStats(
                layer=layer, frontier_vertices=count,
                edges_examined=edges,
                discovered=int(bm.popcount(frontier)),
                active_tiles=-(-e_size // t),
                truncated_edges=int(trunc)))
        layer += 1
    state = BfsState(frontier, visited, parent, jnp.int32(layer))
    return state, stats, log
