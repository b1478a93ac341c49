"""Layer-synchronous parallel top-down BFS — Algorithms 2 and 3.

Thin public wrapper over `core.engine` (the unified traversal engine).
Two scalar expansion flavours survive as the ``algorithm`` switch:

* ``nonsimd`` — Algorithm 2 semantics.  Dense bool arrays for
  in/out/visited: no bit race exists because every vertex owns a whole
  element; only the *benign* parent race of §3.2 remains.
* ``simd``    — Algorithm 3.  Bitmap arrays + the racy parent-mark
  scatter of the hot loop + the **restoration process** (§3.3.2),
  which packs the new frontier from those marks.  No atomics
  anywhere — what made the paper's AVX-512 vectorization legal, and
  equally what makes the XLA/TPU scatter formulation legal.

Both drivers now run the whole search as ONE fused ``lax.while_loop``
on device (no per-layer host sync); pass ``policy=`` to switch the
engine's direction policy, or use `engine.traverse_hostloop` for the
legacy bucketed layer loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import bitmap as bm
from repro.core import engine
from repro.core.csr import Csr, init_visited
# Re-exports: these historically lived here; canonical home is engine.
from repro.core.engine import BfsState, LayerStats, apportion  # noqa: F401


def init_state(csr: Csr, root) -> BfsState:
    v_pad = csr.n_vertices_padded
    frontier = bm.set_bits_exact(bm.zeros(v_pad),
                                 jnp.asarray([root], jnp.int32))
    visited = bm.set_bits_racy(init_visited(csr),
                               jnp.asarray([root], jnp.int32))
    parent = jnp.full((v_pad,), csr.n_vertices, jnp.int32)
    parent = parent.at[root].set(root)
    return BfsState(frontier, visited, parent, jnp.int32(0))


def expand_simd_semantics(colstarts, rows, n_vertices: int,
                          state: BfsState, frontier_size: int,
                          edge_slots: int) -> BfsState:
    """One layer of Algorithm 3 (bitmaps, racy scatter, restoration)."""
    out, visited, parent, _ = engine.scalar_expand(
        colstarts, rows, n_vertices, state.frontier, state.visited,
        state.parent, frontier_size, edge_slots, "simd")
    return BfsState(out, visited, parent, state.layer + 1)


def expand_nonsimd(colstarts, rows, n_vertices: int, state: BfsState,
                   frontier_size: int, edge_slots: int) -> BfsState:
    """One layer of Algorithm 2 on dense bool arrays (exact updates)."""
    out, visited, parent, _ = engine.scalar_expand(
        colstarts, rows, n_vertices, state.frontier, state.visited,
        state.parent, frontier_size, edge_slots, "nonsimd")
    return BfsState(out, visited, parent, state.layer + 1)


def run_bfs(csr: Csr, root, *, algorithm: str = "simd",
            collect_stats: bool = False, max_layers: int = 1024,
            policy=None, tile: int | None = None):
    """Fused single-launch BFS driver (plan-cache-backed).

    Args unchanged from the historical bucketed driver; additionally
    accepts ``policy`` (any `engine` direction policy — default
    `engine.TopDown()`) and ``tile`` for policies that use the SIMD
    kernel.  ``root`` may be a sequence for batched multi-root search
    (state arrays then carry a leading root axis).  Routes through
    `repro.bfs.plan`'s cached `CompiledTraversal` (one trace per
    (geometry, resolved spec)).
    """
    from repro.api.plan import plan as _plan
    spec = engine.make_spec(policy=policy, algorithm=algorithm,
                            tile=tile, max_layers=max_layers)
    res = _plan(csr, spec).run(root)
    if collect_stats:
        return res.state, engine.layer_stats(res)
    return res.state


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def run_bfs_jit(colstarts, rows, root, n_vertices: int,
                algorithm: str = "simd", max_layers: int = 64) -> BfsState:
    """Fully-jitted driver on raw arrays (static full-E shapes).

    Alias for the engine's fused loop; used for ``.lower()``/dry-run
    paths that only have arrays, not a `Csr`.  Builds its spec
    explicitly (a concrete policy — "auto" resolution needs concrete
    degree statistics, unavailable under trace) and routes through the
    plan cache like every other entry.
    """
    from repro.api.spec import TraversalSpec
    res = engine.traverse_arrays(
        colstarts, rows, jnp.reshape(jnp.asarray(root, jnp.int32), (1,)),
        n_vertices=n_vertices,
        spec=TraversalSpec(policy=engine.TopDown(), algorithm=algorithm,
                           max_layers=max_layers))
    st = res.state
    return BfsState(st.frontier[0], st.visited[0], st.parent[0],
                    st.layer)


def parents_graph500(state: BfsState, n_vertices: int) -> jax.Array:
    """Convert internal P (∞ == V sentinel) to Graph500 convention (-1)."""
    p = state.parent[..., :n_vertices]
    return jnp.where(p >= n_vertices, -1, p)
