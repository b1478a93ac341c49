"""Pallas TPU kernel: SIMD frontier compaction (paper §4, queue
generation).

The paper's headline vectorization replaces the per-edge scalar queue
append of Algorithm 2 with a *vector* sequence: test a lane mask,
prefix-sum the mask to rank each surviving lane, and scatter the
survivors to their ranked queue slots in one masked store.  This
kernel is that sequence applied to the engine's native **packed
uint32 bitmap** representation: a packed candidate bitmap goes in, a
dense vertex queue + count comes out, in one pass over ``W = V/32``
words — never materializing the dense ``V``-sized bool/int32 mask
that `core.bitmap.compact` (``unpack_bool`` + ``jnp.nonzero``)
round-trips through HBM every layer.

Structure (the §4 "vectorized queue generation", re-tiled):

* **per-tile popcount** — a tiny jnp planning pass popcounts each
  ``tile_words`` block of the bitmap and exclusive-prefix-sums the
  counts into per-tile *queue base offsets*.  This is O(W) packed-word
  work (V/8 bytes read), the 32x-compressed replacement for the
  full-V scan.
* **scalar-prefetched grid** — the base offsets ride in scalar
  prefetch memory; grid step t DMAs word-block t and already knows
  where its survivors land.
* **in-tile rank-and-scatter** — inside the tile the words unpack
  in-register to a (tile_words, 32) lane matrix; an exclusive prefix
  sum over the bit lanes ranks each set bit (the paper's
  ``_mm512_mask_compressstore`` analogue) and a masked scatter writes
  ``vertex_id`` to ``queue[base[t] + rank]``.

Bits beyond the queue capacity are dropped (``mode="drop"``), exactly
like `bitmap.compact`'s ``size=`` truncation; callers size the queue
from the workload counters (hostloop pow2 buckets) or at V_pad (the
fused engine's static planning queue).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.bitmap import BITS_PER_WORD, word_bits
from repro.kernels import compiler_params

DEFAULT_TILE_WORDS = 256   # 256 words = 8192 bits per grid step


def _rank_scatter(tile_words: int, t, words, base, queue):
    """In-tile rank-and-scatter on a loaded (tile_words,) word block.

    Returns the updated queue.  ``base`` is this tile's exclusive
    global offset (scalar)."""
    bits = word_bits(words).reshape(-1)
    vid = (t * tile_words + jnp.arange(tile_words, dtype=jnp.int32))
    vid = (vid[:, None] * BITS_PER_WORD
           + jnp.arange(BITS_PER_WORD, dtype=jnp.int32)).reshape(-1)
    # exclusive prefix sum over the flattened lanes = queue rank
    rank = jnp.cumsum(bits) - bits
    idx = jnp.where(bits != 0, base + rank, queue.shape[0])
    return queue.at[idx].set(vid, mode="drop")


def _compact_kernel(tile_words: int, fill: int, off_ref, words_ref,
                    q_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        q_ref[...] = jnp.full(q_ref.shape, fill, jnp.int32)

    q_ref[...] = _rank_scatter(tile_words, t, words_ref[...],
                               off_ref[t], q_ref[...])


def _compact_batched_kernel(tile_words: int, fill: int, off_ref,
                            words_ref, q_ref):
    """All roots per grid step: the grid runs over WORD TILES only and
    each step rank-and-scatters every root's (tile_words,) block into
    its queue row.  A root axis on the grid would cost B interpret
    steps per layer (and B sequential steps on a core); the row-wise
    scatter keeps the launch B-independent."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        q_ref[...] = jnp.full(q_ref.shape, fill, jnp.int32)

    words = words_ref[...]                   # (B, tile_words)
    n_batch = words.shape[0]
    bits = word_bits(words).reshape(n_batch, -1)   # (B, tiles * 32)
    vid = (t * tile_words + jnp.arange(tile_words, dtype=jnp.int32))
    vid = (vid[:, None] * BITS_PER_WORD
           + jnp.arange(BITS_PER_WORD, dtype=jnp.int32)).reshape(-1)
    rank = jnp.cumsum(bits, axis=1) - bits   # exclusive, per root
    size = q_ref.shape[1]
    col = jnp.where(bits != 0, off_ref[:, t][:, None] + rank, size)
    row = jnp.broadcast_to(
        jnp.arange(n_batch, dtype=jnp.int32)[:, None], col.shape)
    q_ref[...] = q_ref[...].at[row, col].set(
        jnp.broadcast_to(vid[None, :], col.shape), mode="drop")


def _plan(words, tile_words: int):
    """Per-tile popcounts -> (padded words, exclusive offsets, total).

    The packed planning pass: O(W) on uint32 words, no dense mask."""
    w = words.shape[-1]
    pad = (-w) % tile_words
    if pad:
        z = jnp.zeros(words.shape[:-1] + (pad,), jnp.uint32)
        words = jnp.concatenate([words, z], axis=-1)
    counts = jax.lax.population_count(words).astype(jnp.int32)
    per_tile = counts.reshape(words.shape[:-1] + (-1, tile_words)) \
        .sum(axis=-1, dtype=jnp.int32)
    offs = jnp.cumsum(per_tile, axis=-1, dtype=jnp.int32) - per_tile
    total = per_tile.sum(axis=-1, dtype=jnp.int32)
    return words, offs, total


def vmem_budget(n_batch: int, size: int, tile_words: int) -> int:
    """Bytes of VMEM the kernel pins: the whole (B, size) queue block
    plus the (B, tile_words) word block (double-buffered)."""
    return 4 * n_batch * size + 2 * 4 * n_batch * tile_words


def _budget_check(n_batch: int, size: int, tile_words: int) -> None:
    # local import: ops imports this module
    from repro.kernels.ops import VMEM_BYTES, _VMEM_HEADROOM
    budget = vmem_budget(n_batch, size, tile_words)
    if budget > VMEM_BYTES * _VMEM_HEADROOM:
        raise ValueError(
            f"frontier_compact working set {budget/2**20:.1f} MiB "
            f"exceeds VMEM budget; shard the vertex range across "
            f"chips (core/bfs_distributed.py), reduce the batch "
            f"width, or run the dense arm (packed=False)")


def _tile_words(n_words: int, interpret: bool) -> int:
    """Grid sizing: interpret mode evaluates every grid step in
    Python, so one un-padded step over the whole bitmap is cheapest;
    compiled mode keeps one aligned block per step."""
    if not interpret:
        return min(DEFAULT_TILE_WORDS, max(n_words, 1))
    return max(n_words, 1)


@functools.partial(jax.jit, static_argnames=("size", "fill",
                                             "tile_words", "interpret"))
def frontier_compact(words, *, size: int, fill: int,
                     tile_words: int | None = None,
                     interpret: bool = True):
    """Packed bitmap -> (queue (size,) int32, count scalar int32).

    The queue holds the set-bit vertex ids in ascending order, padded
    with ``fill`` (the sentinel); bits past ``size`` are dropped.
    Drop-in replacement for `core.bitmap.compact` + `popcount` without
    the dense unpack/nonzero round trip.
    """
    if tile_words is None:
        tile_words = _tile_words(words.shape[0], interpret)
    _budget_check(1, size, tile_words)
    words_p, offs, total = _plan(words, tile_words)
    n_tiles = words_p.shape[0] // tile_words

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((tile_words,), lambda t, off: (t,))],
        out_specs=pl.BlockSpec((size,), lambda t, off: (0,)),
    )
    queue = pl.pallas_call(
        functools.partial(_compact_kernel, tile_words, fill),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((size,), jnp.int32),
        compiler_params=compiler_params(
            # accumulating output => sequential grid on the core
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="bfs_frontier_compact",
    )(offs, words_p)
    return queue, total


@functools.partial(jax.jit, static_argnames=("size", "fill",
                                             "tile_words", "interpret"))
def frontier_compact_batched(words, *, size: int, fill: int,
                             tile_words: int | None = None,
                             interpret: bool = True):
    """Batched compaction: (B, W) words -> ((B, size) queues, (B,)
    counts).  The grid runs over word tiles only — every root's block
    is ranked and scattered inside one step, so the launch cost is
    independent of the batch width (one interpret step per tile, not
    B)."""
    if tile_words is None:
        tile_words = _tile_words(words.shape[1], interpret)
    _budget_check(words.shape[0], size, tile_words)
    words_p, offs, total = _plan(words, tile_words)
    n_batch = words_p.shape[0]
    n_tiles = words_p.shape[1] // tile_words

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((n_batch, tile_words),
                               lambda t, off: (0, t))],
        out_specs=pl.BlockSpec((n_batch, size), lambda t, off: (0, 0)),
    )
    queue = pl.pallas_call(
        functools.partial(_compact_batched_kernel, tile_words, fill),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_batch, size), jnp.int32),
        compiler_params=compiler_params(
            # accumulating output => sequential grid on the core
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="bfs_frontier_compact_batched",
    )(offs, words_p)
    return queue, total
