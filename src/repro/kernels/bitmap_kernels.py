"""Pallas TPU kernels for bitmap reductions.

``popcount`` — frontier-size reduction over the bitmap words, tiled
through VMEM with a scalar accumulator.  Used by the BFS drivers for
the termination test (``while in != 0``, Alg. 3 line 7).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import compiler_params

DEFAULT_TILE = 4096


def _popcount_kernel(words_ref, acc_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    counts = jax.lax.population_count(words_ref[...]).astype(jnp.int32)
    acc_ref[...] += counts.sum(keepdims=True)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def popcount(words, *, tile: int = DEFAULT_TILE, interpret: bool = True):
    """Total set bits in a (W,) uint32 bitmap (W padded to tile)."""
    n = words.shape[0]
    pad = (-n) % tile
    if pad:
        words = jnp.concatenate([words, jnp.zeros((pad,), jnp.uint32)])
    n_tiles = words.shape[0] // tile
    out = pl.pallas_call(
        _popcount_kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((tile,), lambda t: (t,))],
        out_specs=pl.BlockSpec((1,), lambda t: (0,)),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
        compiler_params=compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="bitmap_popcount",
    )(words)
    return out[0]
