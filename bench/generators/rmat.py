"""Graph500 Kronecker (R-MAT) generator, the benchmark's own copy.

Kept apart from the program's generator so that a change to the
program cannot change the data a cell runs on.  Graph500 kernel 1:
``V = 2**scale`` vertices, ``round(edgefactor * V)`` generated edge
tuples with the initiator A/B/C/D = 0.57/0.19/0.19/0.05, vertex
labels randomly permuted, then symmetrized to twice as many directed
slots.  Self-loops and duplicates are kept.  The whole graph is made
on the device in one jitted call from the seed.

`for_config` is the generator's entry for the harness: the R-MAT
structure comes from the configuration's ``structure_seed`` alone and
the run's seed relabels the vertices, so every seed gets an
isomorphic graph, the same work under other labels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

A, B, C, D = 0.57, 0.19, 0.19, 0.05


@functools.partial(jax.jit, static_argnums=(1, 2))
def _rmat_pairs(key: jax.Array, scale: int, n_edges: int) -> jax.Array:
    """(2, n_edges) int32 R-MAT endpoints with permuted labels."""
    ab = A + B
    c_norm = C / (C + D)
    a_norm = A / (A + B)
    k_bits, k_perm = jax.random.split(key)
    u = jax.random.uniform(k_bits, (scale, 2, n_edges))
    ii_bit = u[:, 0, :] > ab
    jj_bit = u[:, 1, :] > jnp.where(ii_bit, c_norm, a_norm)
    weights = (jnp.int32(1) << jnp.arange(scale, dtype=jnp.int32))[:, None]
    src = (ii_bit.astype(jnp.int32) * weights).sum(0, dtype=jnp.int32)
    dst = (jj_bit.astype(jnp.int32) * weights).sum(0, dtype=jnp.int32)
    perm = jax.random.permutation(k_perm, jnp.arange(1 << scale,
                                                     dtype=jnp.int32))
    return jnp.stack([perm[src], perm[dst]])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _symmetric(key: jax.Array, scale: int, n_edges: int):
    pairs = _rmat_pairs(key, scale, n_edges)
    return (jnp.concatenate([pairs[0], pairs[1]]),
            jnp.concatenate([pairs[1], pairs[0]]))


def key_for(seed: int) -> jax.Array:
    """``jax.random.key(seed)`` for seeds below 2**32; the bits above
    are folded in, since the key keeps only the low 32 of them."""
    seed = int(seed) % 2**64
    key = jax.random.key(seed % 2**32)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


def generate(seed: int, scale: int, edgefactor: float = 16):
    """Symmetrized R-MAT edge list ``(src, dst)`` as device int32
    arrays, from `key_for(seed)`."""
    return _symmetric(key_for(seed), scale,
                      int(round(edgefactor * (1 << scale))))


@functools.partial(jax.jit, static_argnums=(3,))
def _relabel(key, src, dst, n_vertices: int):
    label = jax.random.permutation(key, jnp.arange(n_vertices,
                                                   dtype=jnp.int32))
    return label[src], label[dst], label


def for_config(config: dict, seed: int):
    """``(src, dst, n_vertices, fixed)``: the configuration's graph for
    a run seed.  The structure comes from ``structure_seed``; the seed
    draws a relabelling ``label`` (structure vertex i is vertex
    ``label[i]``), and ``fixed`` is ``(structure_seed, label)`` with
    ``label`` on the host."""
    scale = int(config["scale"])
    structure_seed = int(config["structure_seed"])
    src, dst = generate(structure_seed, scale, float(config["edgefactor"]))
    src, dst, label = _relabel(jax.random.fold_in(key_for(seed), 1),
                               src, dst, 1 << scale)
    return src, dst, 1 << scale, (structure_seed, np.asarray(label))
