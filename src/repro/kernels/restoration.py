"""Pallas TPU kernel: the restoration process (Alg. 3 lines 15-29).

Repairs the output-queue bitmap after the racy expansion: every vertex
v with ``P[v] < 0`` was discovered this layer (the expansion wrote
``P[v] = u - |V|``); its bit must be present in ``out`` and ``visited``
regardless of which scatter lanes lost their word race.

The paper walks each non-zero 32-bit word and splits it into low/high
16-lane halves to fit the 16-wide VPU.  The TPU formulation instead
views the predecessor array as (V_pad/32, 32) — one bitmap word per
row — tiles it into (tile/32, 32) blocks and packs each row's bits
with a weighted lane sum — the same word-halving idea generalized to
8x128 lanes, with no data-dependent
branching at all (the paper's ``if w != 0`` short-circuit is replaced
by unconditional vector math, which on TPU is cheaper than a branch).

Every tile is independent: the grid is embarrassingly parallel
(dimension_semantics = parallel), unlike the expansion kernel.
Output: fixed P tile + a (tile/32,) uint32 bitmap *delta* that the
caller ORs into both ``out`` and ``visited``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitmap import BITS_PER_WORD
from repro.kernels import compiler_params

DEFAULT_TILE = 16384  # vertices per grid step; 512 words out per step


def _restoration_kernel(n_vertices: int, p_ref, p_out_ref, delta_ref):
    p = p_ref[...]                      # (tile/32, 32): one word per row
    marked = p < 0
    # P[vertex] = P[vertex] + nodes  (line 25)
    p_out_ref[...] = jnp.where(marked, p + n_vertices, p)
    # out.SetBit(vertex) for each marked vertex (lines 23-24), packed
    # along each row.  Mosaic reduces signed integers only: sum the
    # distinct powers of two as int32 (bit 31 wraps to the sign bit)
    # and bitcast back.
    lane = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
    packed = jnp.where(marked, jnp.int32(1) << lane, 0).sum(
        axis=1, keepdims=True, dtype=jnp.int32)
    delta_ref[...] = jax.lax.bitcast_convert_type(packed, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("n_vertices", "tile",
                                             "interpret"))
def restoration(parent, *, n_vertices: int, tile: int = DEFAULT_TILE,
                interpret: bool = True):
    """Run the restoration kernel over the whole P array.

    Args:
      parent: (V_pad,) int32, V_pad a multiple of ``tile``;
        negative entries mark this layer's discoveries.
    Returns:
      (parent_fixed, delta) where delta is the (V_pad/32,) uint32
      bitmap of repaired vertices.
    """
    v_pad = parent.shape[0]
    assert v_pad % tile == 0, "V_pad must be a multiple of the tile"
    assert tile % BITS_PER_WORD == 0
    n_tiles = v_pad // tile
    # one bitmap word per row: the (words, 32) view keeps the packing
    # a lane reduction, with no in-kernel reshape (Mosaic has no
    # (tile,) -> (tile/32, 32) shape cast)
    rows = tile // BITS_PER_WORD
    n_words = v_pad // BITS_PER_WORD

    kernel = functools.partial(_restoration_kernel, n_vertices)
    p_fixed, delta = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((rows, BITS_PER_WORD), lambda t: (t, 0))],
        out_specs=[pl.BlockSpec((rows, BITS_PER_WORD), lambda t: (t, 0)),
                   pl.BlockSpec((rows, 1), lambda t: (t, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((n_words, BITS_PER_WORD), jnp.int32),
            jax.ShapeDtypeStruct((n_words, 1), jnp.uint32)],
        compiler_params=compiler_params(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="bfs_restoration",
    )(parent.reshape(n_words, BITS_PER_WORD))
    return p_fixed.reshape(v_pad), delta.reshape(n_words)
