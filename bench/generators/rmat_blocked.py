"""Graph500 Kronecker (R-MAT) generator for graphs larger than one
chip's scratch memory.

The same kernel 1 as `bench.generators.rmat` (initiator 0.57/0.19/
0.19/0.05, ``round(edgefactor * 2**scale)`` edge tuples, permuted
labels, symmetrized, self-loops and duplicates kept), with two
differences that keep a scale-24 graph inside one chip's memory and
its build quick to compile:

* the ``scale * 2`` uniforms of each edge are drawn ``BLOCK`` edges at
  a time, each block from its own key (`rmat` draws them in one array,
  48 GiB at scale 24);
* the label permutations are keyed bijections of ``[0, 2**scale)``
  (`permute`: rounds of an odd multiply, an xor-shift and an add, each
  one-to-one modulo ``2**scale``), computed per edge, instead of
  `jax.random.permutation` and a gather (a sort whose TPU compile
  alone takes most of a minute).

The draws differ from `rmat`'s, so a configuration names one generator
for good.  `for_config` is the harness's entry, as in `rmat`: the
structure comes from ``structure_seed`` alone and the run's seed
relabels the vertices, so every seed searches an isomorphic graph.

A configuration with ``chips`` > 1 is searched over a 1-D partition:
chip ``d`` owns the ``d``-th of ``chips`` equal ranges of labels, and
the fullest chip's slots set the time of every layer.  The structure
seed's labelling decides which vertices fall in which range; the run's
seed permutes labels only inside each range (`permute_within`), so
every chip holds the same vertices and slots under every seed, and
every seed gets the same work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.generators import rmat

#: edge tuples drawn per block (their uniforms take 8 * scale bytes
#: each: 3 GiB at scale 24)
BLOCK = 1 << 24
#: rounds of `permute`
ROUNDS = 4


def permute(x: jax.Array, key: jax.Array, scale: int) -> jax.Array:
    """A keyed bijection of ``[0, 2**scale)``, elementwise on int32
    ``x``: each round multiplies by an odd number, xors in the high
    half shifted down and adds a constant, all modulo ``2**scale``."""
    mask = jnp.uint32((1 << scale) - 1)
    mul, add = jax.random.bits(key, (2, ROUNDS), jnp.uint32)
    y = x.astype(jnp.uint32)
    for r in range(ROUNDS):
        y = (y * (mul[r] | 1)) & mask
        y = y ^ (y >> ((scale + 1) // 2))
        y = (y + add[r]) & mask
    return y.astype(jnp.int32)


def permute_within(x: jax.Array, key: jax.Array, scale: int,
                   ranges: int) -> jax.Array:
    """`permute` inside each of ``ranges`` (a power of two) equal
    aligned ranges of ``[0, 2**scale)``: the high bits that number the
    range pass through, the low bits are permuted."""
    low = scale - (ranges.bit_length() - 1)
    high = x & jnp.int32(~((1 << low) - 1))
    return high | permute(x & jnp.int32((1 << low) - 1), key, low)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _symmetric(structure_key: jax.Array, run_key: jax.Array, scale: int,
               n_edges: int, ranges: int):
    """The run's symmetrized ``(src, dst)`` and its relabelling
    ``label`` (structure vertex i is vertex ``label[i]``); the run's
    key permutes inside each of ``ranges`` label ranges."""
    k_bits, k_perm = jax.random.split(structure_key)
    weights = (jnp.int32(1) << jnp.arange(scale, dtype=jnp.int32))[:, None]
    block = min(BLOCK, n_edges)

    def relabel(raw):
        return permute_within(permute(raw, k_perm, scale), run_key, scale,
                              ranges)

    def draw(i):
        u = jax.random.uniform(jax.random.fold_in(k_bits, i),
                               (scale, 2, block))
        ii_bit = u[:, 0, :] > rmat.A + rmat.B
        jj_bit = u[:, 1, :] > jnp.where(ii_bit, rmat.C / (rmat.C + rmat.D),
                                        rmat.A / (rmat.A + rmat.B))
        src = (ii_bit.astype(jnp.int32) * weights).sum(0, dtype=jnp.int32)
        dst = (jj_bit.astype(jnp.int32) * weights).sum(0, dtype=jnp.int32)
        return relabel(src), relabel(dst)

    src, dst = jax.lax.map(draw, jnp.arange(-(-n_edges // block)))
    src, dst = src.reshape(-1)[:n_edges], dst.reshape(-1)[:n_edges]
    label = permute_within(jnp.arange(1 << scale, dtype=jnp.int32),
                           run_key, scale, ranges)
    return (jnp.concatenate([src, dst]), jnp.concatenate([dst, src]),
            label)


def for_config(config: dict, seed: int):
    """``(src, dst, n_vertices, fixed)`` as `rmat.for_config` gives
    them: the structure from ``structure_seed``, the labels from the
    run's seed, ``fixed = (structure_seed, label)`` with ``label`` on
    the host.  The run's seed permutes inside each of the
    configuration's ``chips`` label ranges (default 1: all labels)."""
    scale = int(config["scale"])
    structure_seed = int(config["structure_seed"])
    ranges = int(config.get("chips", 1))
    if ranges < 1 or ranges & (ranges - 1) or ranges > 1 << scale:
        raise ValueError(f"chips must be a power of two up to 2**scale, "
                         f"not {ranges}")
    src, dst, label = _symmetric(
        rmat.key_for(structure_seed),
        jax.random.fold_in(rmat.key_for(seed), 1), scale,
        int(round(float(config["edgefactor"]) * (1 << scale))), ranges)
    return src, dst, 1 << scale, (structure_seed, np.asarray(label))
