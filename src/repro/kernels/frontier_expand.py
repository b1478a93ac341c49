"""Pallas TPU kernel: vectorized adjacency-list exploration (Listing 1).

The paper's hot loop, re-tiled for the TPU memory hierarchy:

* the **edge stream** (`nbr`, `cand`, `valid` — the apportioned layer
  adjacency) lives in HBM and is DMA'd tile-by-tile into VMEM by the
  Pallas pipeline (BlockSpec over the grid).  Mosaic double-buffers
  these DMAs — the TPU replacement for the paper's software-prefetch
  intrinsics, with the *block size* playing the role of the prefetch
  distance (swept in EXPERIMENTS §Perf);
* the **bitmaps** (visited, output queue) and the **predecessor array**
  are VMEM-resident for the whole kernel — the payoff of the paper's
  32x bitmap compression on TPU: a SCALE-22 graph's bitmaps + P
  (0.5 MB + 0.5 MB + 16 MB... P dominates; see ``vmem_budget``) fit in
  scratchpad, so every irregular gather/scatter hits VMEM instead of
  HBM.  Larger graphs shard vertex ranges across chips first
  (core/bfs_distributed.py) precisely to preserve this property;
* lane masking replaces AVX-512 mask registers; the sentinel-padded
  tail replaces the peel/remainder loops (csr.py).

Per tile (16 AVX lanes -> 8x128 = 1024 TPU lanes):
  1. load `cand` vertex ids                  (paper: _mm512_load_epi32)
  2. word = cand >> 5, bit = cand & 31       (paper: div/rem)
  3. gather visited & out words              (paper: i32gather)
  4. mask = !(test(vis) | test(out))         (paper: ktest/kor/knot)
  5. masked scatter P[cand] = nbr - |V|      (paper: mask i32scatter)
  6. masked racy word scatter out |= bit     (the §3.3.2 race)

The scatter in step 6 loses colliding-word bits exactly like the
paper's non-atomic scatter; the restoration kernel repairs them.
Grid steps are sequential on a TensorCore, so tile t+1 observes tile
t's updates (the contract pinned by kernels/ref.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitmap import WORD_MASK, WORD_SHIFT
from repro.kernels import compiler_params

DEFAULT_TILE = 1024  # 8 sublanes x 128 lanes of int32


def _expand_tile(n_vertices: int, check_frontier: bool,
                 nbr, cand, valid, frontier, vis, out, p):
    """One tile of the hot loop on loaded VMEM values.

    Shared by the single-root and the batched (leading root-axis)
    kernels.  Returns the updated (out, p) for this tile's writes.
    """
    valid = valid != 0

    # index transformation vertex -> (word, bit)
    word = cand >> WORD_SHIFT
    bit = (cand & WORD_MASK).astype(jnp.uint32)
    bits = jnp.uint32(1) << bit

    w_clip = jnp.clip(word, 0, out.shape[0] - 1)
    vis_words = vis[w_clip]          # i32gather against VMEM bitmap
    out_words = out[w_clip]
    undiscovered = ((vis_words | out_words) & bits) == 0
    mask = valid & undiscovered
    if check_frontier:               # bottom-up direction: test parent
        nw = jnp.clip(nbr >> WORD_SHIFT, 0, frontier.shape[0] - 1)
        nb = (nbr & WORD_MASK).astype(jnp.uint32)
        in_front = (frontier[nw] & (jnp.uint32(1) << nb)) != 0
        mask = mask & in_front

    # masked scatter of P (negative marking) — benign duplicate race
    p_idx = jnp.where(mask, cand, p.shape[0])
    new_p = p.at[p_idx].set(nbr - n_vertices, mode="drop")

    # masked racy word scatter of the output queue (Fig. 6 race)
    new_words = out_words | bits
    w_idx = jnp.where(mask, word, out.shape[0])
    new_out = out.at[w_idx].set(new_words, mode="drop")
    return new_out, new_p


def _expand_kernel(n_vertices: int, check_frontier: bool,
                   nbr_ref, cand_ref, valid_ref, frontier_ref, vis_ref,
                   out0_ref, p0_ref, out_ref, p_ref):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():  # carry initial out/P into the accumulating outputs
        out_ref[...] = out0_ref[...]
        p_ref[...] = p0_ref[...]

    out, p = _expand_tile(n_vertices, check_frontier,
                          nbr_ref[...], cand_ref[...], valid_ref[...],
                          frontier_ref[...], vis_ref[...],
                          out_ref[...], p_ref[...])
    out_ref[...] = out
    p_ref[...] = p


def _expand_batched_kernel(n_vertices: int, check_frontier: bool,
                           nbr_ref, cand_ref, valid_ref, frontier_ref,
                           vis_ref, out0_ref, p0_ref, out_ref, p_ref):
    """Batched variant: grid (roots, tiles); blocks carry a leading
    size-1 root axis.  Each root's tile sequence accumulates into its
    own out/P rows, so roots are independent ("parallel" axis)."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = out0_ref[...]
        p_ref[...] = p0_ref[...]

    out, p = _expand_tile(n_vertices, check_frontier,
                          nbr_ref[0], cand_ref[0], valid_ref[0],
                          frontier_ref[0], vis_ref[0],
                          out_ref[0], p_ref[0])
    out_ref[...] = out[None]
    p_ref[...] = p[None]


def vmem_budget(n_words: int, v_pad: int, tile: int) -> int:
    """Bytes of VMEM the kernel pins (bitmaps x3 + P x2 + stream x3x2)."""
    return 4 * (3 * n_words + 2 * v_pad) + 2 * 3 * 4 * tile


@functools.partial(jax.jit, static_argnames=("n_vertices", "tile",
                                             "check_frontier", "interpret"))
def frontier_expand(nbr, cand, valid, frontier, visited, out_init, p_init,
                    *, n_vertices: int, tile: int = DEFAULT_TILE,
                    check_frontier: bool = False, interpret: bool = True):
    """

    Args:
      nbr, cand, valid: (E_slots,) int32 apportioned edge stream
        (valid as int32 0/1; E_slots must be a multiple of ``tile``).
      frontier, visited, out_init: (W,) uint32 bitmaps.
      p_init: (V_pad,) int32 predecessor array.
      n_vertices: |V| (the paper's ``nodes`` constant).
      check_frontier: False = top-down (Listing 1), True = bottom-up.
      interpret: run the kernel body in interpret mode (CPU validation);
        on a real TPU pass False.
    Returns:
      (out, parent) after the racy expansion (restoration NOT applied).
    """
    n_slots = cand.shape[0]
    assert n_slots % tile == 0, "pad the edge stream to the tile size"
    n_tiles = n_slots // tile
    n_words = visited.shape[0]
    v_pad = p_init.shape[0]

    stream_spec = pl.BlockSpec((tile,), lambda t: (t,))
    whole = lambda n: pl.BlockSpec((n,), lambda t: (0,))

    kernel = functools.partial(_expand_kernel, n_vertices, check_frontier)
    out, parent = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[stream_spec, stream_spec, stream_spec,
                  whole(n_words), whole(n_words), whole(n_words),
                  whole(v_pad)],
        out_specs=[whole(n_words), whole(v_pad)],
        out_shape=[jax.ShapeDtypeStruct((n_words,), jnp.uint32),
                   jax.ShapeDtypeStruct((v_pad,), jnp.int32)],
        compiler_params=compiler_params(
            # accumulating outputs => sequential grid on the core
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="bfs_frontier_expand",
    )(nbr, cand, valid, frontier, visited, out_init, p_init)
    return out, parent


@functools.partial(jax.jit, static_argnames=("n_vertices", "tile",
                                             "check_frontier", "interpret"))
def frontier_expand_batched(nbr, cand, valid, frontier, visited,
                            out_init, p_init, *, n_vertices: int,
                            tile: int = DEFAULT_TILE,
                            check_frontier: bool = False,
                            interpret: bool = True):
    """Multi-root expansion: one launch expands B independent searches.

    Args are the single-root ones with a leading root axis:
      nbr, cand, valid: (B, E_slots) int32 apportioned edge streams.
      frontier, visited, out_init: (B, W) uint32 bitmaps.
      p_init: (B, V_pad) int32 predecessor arrays.
    Returns (out, parent) of shapes (B, W) / (B, V_pad), racy
    (restoration NOT applied) — the same contract as `frontier_expand`
    applied independently per root.

    Grid is (B, n_tiles): the root axis is embarrassingly parallel
    (each root accumulates into its own rows); the tile axis stays
    sequential so later tiles observe earlier tiles' updates.
    """
    n_batch, n_slots = cand.shape
    assert n_slots % tile == 0, "pad the edge stream to the tile size"
    n_tiles = n_slots // tile
    n_words = visited.shape[1]
    v_pad = p_init.shape[1]

    stream_spec = pl.BlockSpec((1, tile), lambda b, t: (b, t))
    whole = lambda n: pl.BlockSpec((1, n), lambda b, t: (b, 0))

    kernel = functools.partial(_expand_batched_kernel, n_vertices,
                               check_frontier)
    out, parent = pl.pallas_call(
        kernel,
        grid=(n_batch, n_tiles),
        in_specs=[stream_spec, stream_spec, stream_spec,
                  whole(n_words), whole(n_words), whole(n_words),
                  whole(v_pad)],
        out_specs=[whole(n_words), whole(v_pad)],
        out_shape=[jax.ShapeDtypeStruct((n_batch, n_words), jnp.uint32),
                   jax.ShapeDtypeStruct((n_batch, v_pad), jnp.int32)],
        compiler_params=compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="bfs_frontier_expand_batched",
    )(nbr, cand, valid, frontier, visited, out_init, p_init)
    return out, parent
