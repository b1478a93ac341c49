"""Bitmap (bit-array) data structure — the paper's §3.3.1.

Vertices are represented as single bits packed into uint32 words
(BITS_PER_WORD = 32), giving the 32x working-set compression the paper
relies on.  On the Xeon Phi this compression improved L2 hit rates; on
TPU it is what lets the whole visited/frontier set of a SCALE-25 graph
(4 MB) live in VMEM next to the vector unit.

All helpers are pure-jnp, shape-static and jittable.  Two flavours of
"scatter bits" are provided:

* ``set_bits_exact``    — deterministic OR-scatter (dense-bool + pack).
  Used by the restoration process and by reference implementations.
* ``set_bits_racy``     — gather-word / OR / scatter-word.  Duplicate
  word indices inside one call lose each other's updates ("some lane
  wins"), which is precisely the paper's *bit race condition* (§3.3.2,
  Fig. 6), as with the paper's non-atomic AVX-512 scatters.  The jnp
  expansion body writes no racy bitmap (it packs the new frontier from
  the parent marks, `engine.expand_candidates`); the root init of
  `engine.traverse_hostloop` and `bfs_parallel` and the race tests
  use it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BITS_PER_WORD = 32
WORD_SHIFT = 5          # log2(BITS_PER_WORD)
WORD_MASK = BITS_PER_WORD - 1

__all__ = [
    "BITS_PER_WORD",
    "num_words",
    "zeros",
    "word_and_bit",
    "test_bits",
    "set_bits_exact",
    "set_bits_racy",
    "pack_bool",
    "unpack_bool",
    "popcount",
    "compact",
    "bit2vertex",
    "word_bits",
    "degree_matrix",
    "masked_degree_sum",
]


def num_words(n_vertices: int) -> int:
    """Number of uint32 words needed to hold ``n_vertices`` bits."""
    return (int(n_vertices) + BITS_PER_WORD - 1) // BITS_PER_WORD


def zeros(n_vertices: int) -> jax.Array:
    """A fresh all-zeros bitmap covering ``n_vertices`` bits."""
    return jnp.zeros((num_words(n_vertices),), dtype=jnp.uint32)


def word_and_bit(vertices: jax.Array):
    """Index transformation vertex -> (word index, bit offset).

    The paper performs this with ``_mm512_div_epi32`` /
    ``_mm512_rem_epi32``; shifts and masks are the TPU-friendly form.
    """
    v = vertices.astype(jnp.int32)
    return v >> WORD_SHIFT, (v & WORD_MASK).astype(jnp.uint32)


def test_bits(bitmap: jax.Array, vertices: jax.Array) -> jax.Array:
    """Gather words and test each vertex's bit (TestBit of Alg. 3).

    Out-of-range vertex ids read word 0 in "clip" mode; callers that
    pad use a sentinel vertex whose bit is pre-set in ``visited`` so
    padding lanes always filter out (our replacement for the paper's
    peel/remainder handling).
    """
    word_idx, bit = word_and_bit(vertices)
    words = bitmap[jnp.clip(word_idx, 0, bitmap.shape[0] - 1)]
    return (words >> bit) & jnp.uint32(1) != 0


def pack_bool(dense: jax.Array) -> jax.Array:
    """Pack a (W*32,) bool array into a (W,) uint32 bitmap. Exact."""
    n = dense.shape[0]
    assert n % BITS_PER_WORD == 0, "pad to a word multiple first"
    bits = dense.reshape(-1, BITS_PER_WORD).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(BITS_PER_WORD, dtype=jnp.uint32))
    return (bits * weights).sum(axis=1, dtype=jnp.uint32)


def word_bits(words: jax.Array) -> jax.Array:
    """Expand packed words into per-bit lanes: (..., W) uint32 ->
    (..., W, 32) int32 of 0/1.

    The single home of the word->lanes bit expansion shared by
    `unpack_bool`, `masked_degree_sum` and the compaction kernel's
    in-register rank-and-scatter (kernels/compact.py) — any change to
    the bit order or word width happens here once."""
    shifts = jnp.arange(BITS_PER_WORD, dtype=jnp.uint32)
    return ((words[..., None] >> shifts) & jnp.uint32(1)) \
        .astype(jnp.int32)


def unpack_bool(bitmap: jax.Array) -> jax.Array:
    """Expand a (W,) uint32 bitmap into a (W*32,) bool array. Exact."""
    return word_bits(bitmap).reshape(-1).astype(bool)


def set_bits_exact(bitmap: jax.Array, vertices: jax.Array,
                   valid: jax.Array | None = None) -> jax.Array:
    """Deterministic OR of the given vertices' bits into the bitmap.

    Implemented as a dense-bool scatter (duplicate ``set(True)`` is
    idempotent) followed by a pack.  This is the primitive used by the
    *restoration process* — it plays the role of the paper's per-word
    bit walk (Alg. 3 lines 16-29) but is exact and vectorized.
    """
    n = bitmap.shape[0] * BITS_PER_WORD
    v = vertices.astype(jnp.int32)
    if valid is not None:
        # route invalid lanes out of range; 'drop' mode discards them
        v = jnp.where(valid, v, n)
    dense = jnp.zeros((n,), dtype=bool).at[v].set(True, mode="drop")
    return bitmap | pack_bool(dense)


def set_bits_racy(bitmap: jax.Array, vertices: jax.Array,
                  valid: jax.Array | None = None) -> jax.Array:
    """Racy word-level OR-scatter — the paper's non-atomic SetBit.

    Each lane reads its word (pre-update), ORs its bit, and scatters
    the word back.  When several lanes target the same word, one lane's
    write wins and the others' bits are lost — the *bit race condition*
    of §3.3.2.  The restoration process repairs this from ``P``.
    """
    word_idx, bit = word_and_bit(vertices)
    if valid is not None:
        word_idx = jnp.where(valid, word_idx, bitmap.shape[0])  # dropped
    gathered = bitmap[jnp.clip(word_idx, 0, bitmap.shape[0] - 1)]
    updated = gathered | (jnp.uint32(1) << bit)
    return bitmap.at[word_idx].set(updated, mode="drop")


def popcount(bitmap: jax.Array) -> jax.Array:
    """Total number of set bits (frontier size)."""
    return jax.lax.population_count(bitmap).astype(jnp.int32).sum()


def compact(bitmap: jax.Array, size: int, fill_value: int) -> jax.Array:
    """Bitmap -> padded list of set-bit vertex ids (the input list).

    Returns exactly ``size`` int32 ids, padded with ``fill_value``.
    This is the queue-to-layer conversion of §3: vertices inside one
    layer may be emitted in any order, so a vectorized bit-expansion +
    nonzero compaction is legal.
    """
    dense = unpack_bool(bitmap)
    (idx,) = jnp.nonzero(dense, size=size, fill_value=fill_value)
    return idx.astype(jnp.int32)


def bit2vertex(word_idx: jax.Array, bit: jax.Array) -> jax.Array:
    """Inverse index transformation (bit2vertex of Alg. 3)."""
    return (word_idx.astype(jnp.int32) << WORD_SHIFT) | bit.astype(jnp.int32)


def degree_matrix(degrees: jax.Array, n_bits: int) -> jax.Array:
    """(V,) degrees -> (W, 32) word-aligned degree matrix.

    The loop constant `masked_degree_sum` consumes: row w holds the
    degrees of the 32 vertices packed into bitmap word w (zero for
    padding vertices), so the Table 1 edge counter becomes a word-local
    product against the packed bitmap — no dense V-mask round trip.
    """
    deg = jnp.zeros((n_bits,), jnp.int32).at[:degrees.shape[0]] \
        .set(degrees.astype(jnp.int32))
    return deg.reshape(-1, BITS_PER_WORD)


def masked_degree_sum(words: jax.Array, deg_mat: jax.Array) -> jax.Array:
    """Σ deg over the set bits of a packed bitmap (Table 1 "Edges").

    ``deg_mat`` is `degree_matrix(degrees, W * 32)`.  Consumes the
    packed words directly (the `word_bits` expansion fuses into the
    reduction) — the engine's Table 1 counter without carrying a
    dense (V,) int32 mask through the layer.
    """
    return (word_bits(words) * deg_mat).sum(dtype=jnp.int32)
