"""Distributed BFS across a TPU mesh — the paper's "multi-device
solutions that will be needed to tackle very large graph-based
datasets" (§1), built out.

Decomposition (Graph500 1-D): vertices are striped in contiguous
ranges of ``v_loc`` per chip; each chip owns the *out-edges* of its
range (a rebased CSR slice).  The frontier/visited bitmaps and the
predecessor array are replicated — at bitmap compression (32
vertices/word) a SCALE-27 frontier costs 16 MB/chip, which is what
makes replication affordable and is the distributed payoff of the
paper's §3.3.1 data structure.

Per layer, under ``shard_map`` over the full mesh:
  1. each chip sweeps its local adjacency in rows order, gating every
     edge on its slice of the (replicated) frontier bitmap
     (`engine.rowsweep_stream` — the fused-gather pipeline's jnp arm;
     no compaction/apportionment intermediates) — all compute local;
  2. local discoveries are written into an *encoded parent-candidate*
     array (``INF = V`` for "no update", else the parent id) with a
     deterministic ``.at[].min`` to resolve intra-chip duplicates;
  3. one ``lax.pmin`` all-reduce merges candidates across chips —
     min-parent is deterministic, so unlike the single-chip algorithm
     the distributed tree is reproducible run-to-run;
  4. every chip then derives the next frontier bitmap, visited update,
     and P update locally from the merged candidates.

Collective cost: ONE all-reduce of ``4*V`` bytes per layer, ~7 layers
per RMAT BFS — the collective roofline term is negligible next to the
local edge streaming (EXPERIMENTS.md §Roofline-BFS), which is why 1-D
suffices here and 2-D decompositions buy nothing until V outgrows
replication.

The whole search is one ``lax.while_loop`` of static shape, so it
lowers/compiles for the production meshes in launch/dryrun.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import bitmap as bm
from repro.core import engine
from repro.core.csr import Csr, round_up


# ---------------------------------------------------------------------------
# Host-side partitioner (Graph500 kernel-2 equivalent for the mesh)
# ---------------------------------------------------------------------------

def partition_sizes(n_vertices: int, n_edges_directed: int,
                    n_devices: int, slack: float = 1.5):
    """Static (v_loc, e_loc) partition shapes.

    v_loc: owned vertex range per chip (128-aligned).
    e_loc: per-chip edge capacity — balanced share times ``slack`` to
      absorb RMAT degree skew (measured ~1.3 at SCALE 20, D=256).
    """
    v_loc = round_up(math.ceil(n_vertices / n_devices), 128)
    e_loc = round_up(math.ceil(n_edges_directed / n_devices * slack), 128)
    return v_loc, e_loc


def partition_csr(csr: Csr, n_devices: int, slack: float = 1.5):
    """Split a CSR into per-device contiguous vertex ranges (numpy).

    Returns (rows_sh (D, e_loc), colstarts_sh (D, v_loc+1)).

    The per-device edge capacity is the *measured* maximum over ranges
    (128-aligned) — real data beats the ``slack`` heuristic, which only
    sizes spec-only dry-runs (``partition_sizes``).  RMAT degree skew
    makes the max noticeably above the balanced share at small
    scale/device counts; the measured imbalance is reported by
    benchmarks/affinity.py and attacked in §Perf (equal-edge split).
    """
    v = csr.n_vertices
    v_loc, _ = partition_sizes(v, csr.n_edges, n_devices, slack)
    cs = np.asarray(csr.colstarts)
    rows = np.asarray(csr.rows)
    bounds = [(min(d * v_loc, v), min(d * v_loc + v_loc, v))
              for d in range(n_devices)]
    e_loc = round_up(max(int(cs[hi] - cs[lo]) for lo, hi in bounds), 128)
    rows_sh = np.full((n_devices, e_loc), v, dtype=np.int32)
    colstarts_sh = np.zeros((n_devices, v_loc + 1), dtype=np.int32)
    for d, (lo, hi) in enumerate(bounds):
        local_cs = cs[lo:hi + 1] - cs[lo]
        n_local_edges = int(local_cs[-1]) if len(local_cs) else 0
        colstarts_sh[d, :len(local_cs)] = local_cs
        colstarts_sh[d, len(local_cs):] = local_cs[-1] if len(local_cs) \
            else 0
        rows_sh[d, :n_local_edges] = rows[cs[lo]:cs[hi]]
    return jnp.asarray(rows_sh), jnp.asarray(colstarts_sh)


# ---------------------------------------------------------------------------
# The per-chip program
# ---------------------------------------------------------------------------

def _local_step(rows_l, colstarts_l, frontier, visited, v_loc: int,
                n_vertices: int, v_cap: int, base):
    """One chip's expansion, built from the engine's step pieces:
    `engine.rowsweep_stream` gathers the local frontier slice's
    adjacency in rows order (LOCAL owner ids, GLOBAL neighbor ids) —
    the per-chip arm of the ISSUE 3 fused pipeline: one pass over the
    local rows with a per-edge bitmap gate, no compaction and no
    marker/prefix-sum intermediates — and `engine.candidate_scatter`
    encodes discoveries as the min-parent candidate array the
    collective merge resolves deterministically."""
    w_loc = v_loc // bm.BITS_PER_WORD
    local_words = jax.lax.dynamic_slice(
        frontier, (base // bm.BITS_PER_WORD,), (w_loc,))
    u_loc, v_nbr, valid = engine.rowsweep_stream(
        colstarts_l, rows_l, local_words, v_loc,
        nbr_limit=n_vertices)
    # u is consumed only under ``valid`` by the candidate scatter, so
    # the unconditional rebase is safe for padding slots
    u_glob = u_loc + base
    return engine.candidate_scatter(u_glob, v_nbr, valid, visited,
                                    n_vertices, v_cap)


def make_bfs_program(v_loc: int, n_vertices: int, n_devices: int,
                     axis_names: tuple[str, ...], max_layers: int = 64,
                     merge: str = "allreduce",
                     single_layer: bool = False):
    """Build the shard_map-able per-chip BFS program (static shapes).

    merge = "allreduce" — the baseline: one dense ``pmin`` over the
      full (V,) candidate array per layer (replicated P everywhere).
      Wire bytes/layer ~= 2 * 4V * (g-1)/g.

    merge = "owner" — §Perf optimization (owner-computes, the Graph500
      1-D classic): parent candidates are exchanged with ONE
      ``all_to_all`` so each chip min-reduces only the slice of P it
      owns, then the (32x smaller) frontier *bitmap* is all-gathered
      for the next layer's edge selection.  Wire bytes/layer ~=
      4V * (g-1)/g + V/8 — measured 1.94x less than the baseline and
      P memory drops from V to V/D per chip (EXPERIMENTS.md §Perf).
      The returned parent array is the LOCAL slice (v_loc,).

    merge = "packed" — ISSUE 4's packed-word exchange: the ONLY
      per-layer collective is an all-gather + OR of the 32x-compressed
      *discovered bitmap* (V/8 bytes — int32 candidate masks never hit
      the wire inside the loop).  Parent candidates accumulate
      locally as a running min; a vertex only ever receives candidates
      in the single layer before its bit enters the globally merged
      visited bitmap, so ONE post-loop ``pmin`` resolves parents to
      exactly the per-layer-pmin tree (deterministic).  Wire
      bytes/layer ~= V/8 * (g-1)/g + one final 4V — the win scales
      with the diameter.
    """
    if merge not in ("allreduce", "owner", "packed"):
        raise ValueError(f"unknown merge {merge!r}; expected "
                         f"'allreduce', 'owner' or 'packed'")
    v_cap = v_loc * n_devices
    assert v_cap >= n_vertices
    w_cap = v_cap // bm.BITS_PER_WORD
    w_loc = v_loc // bm.BITS_PER_WORD
    inf = jnp.int32(n_vertices)

    def program(rows_l, colstarts_l, root):
        rows_l = rows_l.reshape(-1)
        colstarts_l = colstarts_l.reshape(-1)
        d = jax.lax.axis_index(axis_names).astype(jnp.int32)
        base = d * v_loc

        frontier = bm.set_bits_exact(
            jnp.zeros((w_cap,), jnp.uint32), root.astype(jnp.int32))
        visited = frontier

        def cond(s):
            return (bm.popcount(s[0]) > 0) & (s[3] < max_layers)

        if merge == "allreduce":
            parent = (jnp.full((v_cap,), inf, jnp.int32)
                      .at[root].set(root.astype(jnp.int32)))

            def body(s):
                frontier, visited, parent, layer = s
                cand = _local_step(rows_l, colstarts_l, frontier,
                                   visited, v_loc, n_vertices, v_cap,
                                   base)
                merged = jax.lax.pmin(cand, axis_names)  # ONE collective
                newly = merged < inf
                new_frontier = bm.pack_bool(newly)
                return (new_frontier, visited | new_frontier,
                        jnp.where(newly, merged, parent), layer + 1)

            state = (frontier, visited, parent, jnp.int32(0))
            if single_layer:   # roofline probe: exact per-layer costs
                frontier, visited, parent, layer = body(state)
            else:
                frontier, visited, parent, layer = jax.lax.while_loop(
                    cond, body, state)
            return parent, layer

        if merge == "packed":
            # packed-word exchange: discoveries cross chips as OR'd
            # uint32 bitmap words; parents stay local until the end.
            frontier = jax.lax.pcast(frontier, axis_names, to="varying")
            visited = jax.lax.pcast(visited, axis_names, to="varying")
            parent_acc = (jnp.full((v_cap,), inf, jnp.int32)
                          .at[root].set(root.astype(jnp.int32)))
            parent_acc = jax.lax.pcast(parent_acc, axis_names, to="varying")

            def body(s):
                frontier, visited, parent_acc, layer = s
                cand = _local_step(rows_l, colstarts_l, frontier,
                                   visited, v_loc, n_vertices, v_cap,
                                   base)
                parent_acc = jnp.minimum(parent_acc, cand)
                newly_l = bm.pack_bool(cand < inf)   # local, V/8 B
                gathered = jax.lax.all_gather(
                    newly_l, axis_names).reshape(n_devices, w_cap)
                merged = functools.reduce(
                    jnp.bitwise_or,
                    [gathered[d] for d in range(n_devices)])
                return (merged, visited | merged, parent_acc,
                        layer + 1)

            state = (frontier, visited, parent_acc, jnp.int32(0))
            if single_layer:   # roofline probe: exact per-layer costs
                frontier, visited, parent_acc, layer = body(state)
            else:
                frontier, visited, parent_acc, layer = \
                    jax.lax.while_loop(cond, body, state)
            # ONE dense collective for the whole search
            parent = jax.lax.pmin(parent_acc, axis_names)
            return parent, layer

        # owner-computes: P holds only this chip's vertex range.
        # The carried bitmaps become device-varying after the first
        # all_gather; mark the (replicated) initial values as varying
        # so the while_loop carry types match.
        frontier = jax.lax.pcast(frontier, axis_names, to="varying")
        visited = jax.lax.pcast(visited, axis_names, to="varying")
        in_range = (root >= base) & (root < base + v_loc)
        parent_l = jnp.full((v_loc,), inf, jnp.int32)
        parent_l = jnp.where(
            in_range,
            parent_l.at[jnp.clip(root - base, 0, v_loc - 1)]
            .set(root.astype(jnp.int32)),
            parent_l)

        def body(s):
            frontier, visited, parent_l, layer = s
            cand = _local_step(rows_l, colstarts_l, frontier, visited,
                               v_loc, n_vertices, v_cap, base)
            # exchange: row j of (D, v_loc) -> chip j; received rows =
            # every chip's candidates for MY vertex range
            cand = cand.reshape(n_devices, v_loc)
            mine = jax.lax.all_to_all(cand, axis_names, split_axis=0,
                                      concat_axis=0, tiled=True)
            merged_l = mine.reshape(n_devices, v_loc).min(axis=0)
            newly_l = (merged_l < inf) & (parent_l == inf)
            parent_l = jnp.where(newly_l, merged_l, parent_l)
            # 32x-compressed frontier broadcast (the paper's bitmap
            # compression is what makes this cheap)
            front_l = bm.pack_bool(newly_l)
            new_frontier = jax.lax.all_gather(
                front_l, axis_names, tiled=True).reshape(w_cap)
            return (new_frontier, visited | new_frontier, parent_l,
                    layer + 1)

        state = (frontier, visited, parent_l, jnp.int32(0))
        if single_layer:       # roofline probe: exact per-layer costs
            frontier, visited, parent_l, layer = body(state)
        else:
            frontier, visited, parent_l, layer = jax.lax.while_loop(
                cond, body, state)
        return parent_l, layer

    return program


# ---------------------------------------------------------------------------
# Mesh-facing wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("merge", "mesh", "axis_names",
                                             "n_vertices", "max_layers"))
def _run(mesh, axis_names, n_vertices, max_layers, merge, rows_sh,
         colstarts_sh, root):
    n_devices = int(np.prod([mesh.shape[a] for a in axis_names]))
    v_loc = int(colstarts_sh.shape[1]) - 1
    program = make_bfs_program(v_loc, n_vertices, n_devices, axis_names,
                               max_layers, merge=merge)
    p_out = P(axis_names) if merge == "owner" else P()
    shard = jax.shard_map(
        program, mesh=mesh,
        in_specs=(P(axis_names), P(axis_names), P()),
        out_specs=(p_out, P()))
    return shard(rows_sh, colstarts_sh, root)


def run_bfs_distributed(csr: Csr, root: int, mesh,
                        axis_names: tuple[str, ...] | None = None,
                        max_layers: int | None = None,
                        slack: float = 1.5,
                        merge: str | None = None, spec=None):
    """Partition + run the distributed BFS on a mesh. Returns (P, depth_count).

    The per-chip program derives from the same resolved
    `TraversalSpec` as every single-chip entry point: pass ``spec=``
    and its ``merge``/``max_layers`` fields govern the exchange
    flavour and layer budget (``merge="auto"`` resolves to "packed",
    the wire-optimal full-tree merge).  The loose ``max_layers=`` /
    ``merge=`` kwargs keep their historical defaults (64,
    "allreduce") and may not be mixed with ``spec=``.

    P follows the internal convention (INF == V for unreached); use
    ``jnp.where(p >= V, -1, p)`` for Graph500 convention.  With
    merge="owner" (§Perf optimization) each chip keeps only its P
    slice during the search; the concatenated result is identical.
    """
    if spec is not None:
        if max_layers is not None or merge is not None:
            raise ValueError(
                "run_bfs_distributed: pass either spec= or the loose "
                "max_layers=/merge= knobs, not both")
        from repro.api.spec import as_format, warn_mesh_ignored_fields
        warn_mesh_ignored_fields(spec, "run_bfs_distributed")
        # the program never reads policy: pin an arbitrary concrete
        # one before resolving so policy="auto" doesn't pay the
        # autotune degree measurement per launch
        probe = (spec.replace(policy="topdown")
                 if spec.policy == "auto" else spec)
        resolved = probe.resolve(as_format(csr))
        max_layers, merge = resolved.max_layers, resolved.merge
    else:
        max_layers = 64 if max_layers is None else max_layers
        merge = "allreduce" if merge is None else merge
    axis_names = axis_names or tuple(mesh.axis_names)
    n_devices = int(np.prod([mesh.shape[a] for a in axis_names]))
    rows_sh, colstarts_sh = partition_csr(csr, n_devices, slack)
    parent, layers = _run(mesh, axis_names, csr.n_vertices, max_layers,
                          merge, rows_sh, colstarts_sh,
                          jnp.asarray(root, jnp.int32))
    return parent[:csr.n_vertices], layers
