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

The partition (`partition_on_mesh`) is built once, on the device:
each chip cuts its slice out of the CSR and expands its colstarts
into the owner of every local slot (`engine.edge_owners`), so the
layer loop never rebuilds owners.

Per layer, under ``shard_map`` over the full mesh:
  1. each chip sweeps its local slots in rows order, gating every
     slot on its owner's bit in its slice of the (replicated)
     frontier bitmap (`engine.owner_gate`) — all compute local;
  2. local discoveries are written into an *encoded parent-candidate*
     array (``INF = V`` for "no update", else the parent id) with a
     deterministic ``.at[].min`` to resolve intra-chip duplicates
     (`engine.candidate_scatter`);
  3. the chips exchange what they found (``merge``, see
     `make_bfs_program`) — min-parent is deterministic, so unlike the
     single-chip algorithm the distributed tree is reproducible
     run-to-run;
  4. every chip then derives the next frontier bitmap, visited update,
     and P update locally from the merged candidates.

Per slot and layer that is two gathers (the owner's frontier word,
the neighbor's visited word) and one scatter, as on one chip.  The
named scopes ``bfs.expand`` (steps 1-2) and ``bfs.exchange`` (3-4)
mark the two phases in profiles.

The whole search is one ``lax.while_loop`` of static shape, so it
lowers/compiles for the production meshes in launch/dryrun.py.
"""
from __future__ import annotations

import functools
import math
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import bitmap as bm
from repro.core import engine
from repro.core.csr import Csr, round_up

MERGES = ("allreduce", "owner", "packed")


# ---------------------------------------------------------------------------
# Partition (Graph500 kernel-2 equivalent for the mesh)
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


def local_slots(csr: Csr, n_devices: int) -> tuple[int, np.ndarray]:
    """``(v_loc, slots)``: the owned vertex range per chip and each
    chip's count of real local slots.  Reads ``colstarts`` (4 V bytes)
    to the host once; ``rows`` stays on the device."""
    v = csr.n_vertices
    v_loc, _ = partition_sizes(v, csr.n_edges, n_devices)
    bounds = np.minimum(np.arange(n_devices + 1) * v_loc, v)
    return v_loc, np.diff(np.asarray(csr.colstarts)[bounds])


def _chip_slice(rows, colstarts, d, v_loc: int, e_loc: int,
                n_vertices: int):
    """Chip ``d``'s rebased CSR slice: its ``e_loc`` rows slots (the
    sentinel past its own edges) and its ``v_loc + 1`` colstarts (the
    last one repeated past the end of the graph)."""
    lo = jnp.minimum(d * v_loc, n_vertices)
    cs = colstarts[jnp.minimum(lo + jnp.arange(v_loc + 1), n_vertices)]
    local_cs = cs - cs[0]
    slot = jnp.arange(e_loc)
    rows_l = jnp.where(
        slot < local_cs[-1],
        rows[jnp.minimum(cs[0] + slot, rows.shape[0] - 1)], n_vertices)
    return rows_l, local_cs


def partition_csr(csr: Csr, n_devices: int):
    """Split a CSR into per-device contiguous vertex ranges, on the
    default device.

    Returns (rows_sh (D, e_loc), colstarts_sh (D, v_loc+1)).

    The per-device edge capacity is the *measured* maximum over ranges
    (128-aligned) — real data beats the ``slack`` heuristic, which only
    sizes spec-only dry-runs (``partition_sizes``).  RMAT degree skew
    makes the max noticeably above the balanced share at small
    scale/device counts; the measured imbalance is reported by
    benchmarks/affinity.py.
    """
    v_loc, slots = local_slots(csr, n_devices)
    e_loc = round_up(max(int(slots.max()), 1), 128)
    cut = jax.vmap(functools.partial(_chip_slice, v_loc=v_loc, e_loc=e_loc,
                                     n_vertices=csr.n_vertices),
                   in_axes=(None, None, 0))
    return jax.jit(cut)(csr.rows, csr.colstarts, jnp.arange(n_devices))


class MeshPartition(NamedTuple):
    """A CSR laid out over a mesh, each array sharded on its leading
    (device) axis: ``rows`` (D, e_loc) neighbor ids, ``colstarts``
    (D, v_loc+1) local offsets, ``owners`` (D, e_loc) the local owner
    id of every slot.  ``slots`` holds each chip's real slot count
    (host)."""
    mesh: jax.sharding.Mesh
    axis_names: tuple[str, ...]
    n_vertices: int
    rows: jax.Array
    colstarts: jax.Array
    owners: jax.Array
    slots: np.ndarray

    @property
    def n_devices(self) -> int:
        return int(self.rows.shape[0])

    @property
    def v_loc(self) -> int:
        return int(self.colstarts.shape[1]) - 1

    @property
    def v_cap(self) -> int:
        return self.v_loc * self.n_devices


def partition_on_mesh(csr: Csr, mesh,
                      axis_names: tuple[str, ...] | None = None
                      ) -> MeshPartition:
    """Partition ``csr`` over ``mesh`` on the device: every chip cuts
    its own slice out of the (replicated) CSR and expands its owner
    stream once.  Records the span ``bfs.mesh.partition`` and the
    gauges ``bfs.mesh.slots_max`` / ``bfs.mesh.slots_mean`` (real
    slots per chip) and ``bfs.mesh.partition_s``."""
    from repro.obs import trace
    from repro.obs.metrics import get_registry
    axis_names = axis_names or tuple(mesh.axis_names)
    n_devices = int(np.prod([mesh.shape[a] for a in axis_names]))
    t0 = time.perf_counter()
    with trace.span("bfs.mesh.partition", devices=n_devices,
                    slots=csr.n_edges):
        v_loc, slots = local_slots(csr, n_devices)
        e_loc = round_up(max(int(slots.max()), 1), 128)

        def cut(rows, colstarts):
            d = jax.lax.axis_index(axis_names)
            rows_l, cs_l = _chip_slice(rows, colstarts, d, v_loc, e_loc,
                                       csr.n_vertices)
            owners_l = engine.edge_owners(cs_l, e_loc, v_loc)
            return rows_l[None], cs_l[None], owners_l[None]

        shard = P(axis_names)
        rows_sh, cs_sh, owners_sh = jax.block_until_ready(jax.jit(
            jax.shard_map(cut, mesh=mesh, in_specs=(P(), P()),
                          out_specs=(shard, shard, shard)))(
            csr.rows, csr.colstarts))
    reg = get_registry()
    reg.gauge("bfs.mesh.slots_max",
              "real CSR slots on the fullest chip").set(int(slots.max()))
    reg.gauge("bfs.mesh.slots_mean",
              "real CSR slots per chip, mean").set(float(slots.mean()))
    reg.gauge("bfs.mesh.partition_s",
              "seconds to build the last mesh partition").set(
        time.perf_counter() - t0)
    return MeshPartition(mesh, axis_names, csr.n_vertices, rows_sh, cs_sh,
                         owners_sh, slots)


# ---------------------------------------------------------------------------
# The per-chip program
# ---------------------------------------------------------------------------

def _local_step(rows_l, owners_l, frontier, visited, v_loc: int,
                n_vertices: int, v_cap: int, base):
    """One chip's expansion over its slots in rows order: the owner
    gate (`engine.owner_gate` on the chip's slice of the frontier,
    LOCAL owner ids, GLOBAL neighbor ids) and
    `engine.candidate_scatter`, which encodes discoveries as the
    min-parent candidate array the exchange resolves
    deterministically."""
    w_loc = v_loc // bm.BITS_PER_WORD
    local_words = jax.lax.dynamic_slice(
        frontier, (base // bm.BITS_PER_WORD,), (w_loc,))
    valid = engine.owner_gate(owners_l, rows_l, local_words, n_vertices)
    # owners are consumed only under ``valid`` by the candidate
    # scatter, so the unconditional rebase is safe for padding slots
    return engine.candidate_scatter(owners_l + base, rows_l, valid,
                                    visited, n_vertices, v_cap)


def make_bfs_program(v_loc: int, n_vertices: int, n_devices: int,
                     axis_names: tuple[str, ...], max_layers: int = 64,
                     merge: str = "allreduce",
                     single_layer: bool = False):
    """Build the shard_map-able per-chip BFS program (static shapes):
    ``program(rows_l, owners_l, root) -> (parent, layers)``.

    merge = "allreduce" — the baseline: one dense ``pmin`` over the
      full (V,) candidate array per layer (replicated P everywhere).

    merge = "owner" — owner-computes, the Graph500 1-D classic: parent
      candidates are exchanged with ONE ``all_to_all`` so each chip
      min-reduces only the slice of P it owns, then the (32x smaller)
      frontier *bitmap* is all-gathered for the next layer's edge
      selection.  P memory drops from V to V/D per chip.  The returned
      parent array is the LOCAL slice (v_loc,).

    merge = "packed" — the packed-word exchange: the ONLY per-layer
      collective is an all-gather + OR of the 32x-compressed
      *discovered bitmap* (int32 candidate masks never hit the wire
      inside the loop).  Parent candidates accumulate locally as a
      running min; a vertex only ever receives candidates in the
      single layer before its bit enters the globally merged visited
      bitmap, so ONE post-loop ``pmin`` resolves parents to exactly the
      per-layer-pmin tree (deterministic).

    `exchange_bytes` gives each flavour's bytes on the wire.
    """
    if merge not in MERGES:
        raise ValueError(f"unknown merge {merge!r}; expected "
                         f"'allreduce', 'owner' or 'packed'")
    v_cap = v_loc * n_devices
    assert v_cap >= n_vertices
    w_cap = v_cap // bm.BITS_PER_WORD
    inf = jnp.int32(n_vertices)

    def program(rows_l, owners_l, root):
        rows_l = rows_l.reshape(-1)
        owners_l = owners_l.reshape(-1)
        d = jax.lax.axis_index(axis_names).astype(jnp.int32)
        base = d * v_loc

        frontier = bm.set_bits_exact(
            jnp.zeros((w_cap,), jnp.uint32), root.astype(jnp.int32))
        visited = frontier

        def expand(frontier, visited):
            with jax.named_scope("bfs.expand"):
                return _local_step(rows_l, owners_l, frontier, visited,
                                   v_loc, n_vertices, v_cap, base)

        def cond(s):
            return (bm.popcount(s[0]) > 0) & (s[3] < max_layers)

        def loop(body, state):
            if single_layer:   # roofline probe: exact per-layer costs
                return body(state)
            return jax.lax.while_loop(cond, body, state)

        if merge == "allreduce":
            parent = (jnp.full((v_cap,), inf, jnp.int32)
                      .at[root].set(root.astype(jnp.int32)))

            def body(s):
                frontier, visited, parent, layer = s
                cand = expand(frontier, visited)
                with jax.named_scope("bfs.exchange"):
                    merged = jax.lax.pmin(cand, axis_names)
                    newly = merged < inf
                    new_frontier = bm.pack_bool(newly)
                    return (new_frontier, visited | new_frontier,
                            jnp.where(newly, merged, parent), layer + 1)

            _, _, parent, layer = loop(
                body, (frontier, visited, parent, jnp.int32(0)))
            return parent, layer

        # the carried bitmaps become device-varying after the first
        # all_gather; mark the (replicated) initial values as varying
        # so the while_loop carry types match
        frontier = jax.lax.pcast(frontier, axis_names, to="varying")
        visited = jax.lax.pcast(visited, axis_names, to="varying")

        if merge == "packed":
            parent_acc = (jnp.full((v_cap,), inf, jnp.int32)
                          .at[root].set(root.astype(jnp.int32)))
            parent_acc = jax.lax.pcast(parent_acc, axis_names, to="varying")

            def body(s):
                frontier, visited, parent_acc, layer = s
                cand = expand(frontier, visited)
                with jax.named_scope("bfs.exchange"):
                    parent_acc = jnp.minimum(parent_acc, cand)
                    newly_l = bm.pack_bool(cand < inf)   # local, V/8 B
                    gathered = jax.lax.all_gather(
                        newly_l, axis_names).reshape(n_devices, w_cap)
                    merged = functools.reduce(
                        jnp.bitwise_or,
                        [gathered[d] for d in range(n_devices)])
                    return (merged, visited | merged, parent_acc,
                            layer + 1)

            _, _, parent_acc, layer = loop(
                body, (frontier, visited, parent_acc, jnp.int32(0)))
            with jax.named_scope("bfs.exchange"):
                # ONE dense collective for the whole search
                return jax.lax.pmin(parent_acc, axis_names), layer

        # owner-computes: P holds only this chip's vertex range.
        in_range = (root >= base) & (root < base + v_loc)
        parent_l = jnp.full((v_loc,), inf, jnp.int32)
        parent_l = jnp.where(
            in_range,
            parent_l.at[jnp.clip(root - base, 0, v_loc - 1)]
            .set(root.astype(jnp.int32)),
            parent_l)

        def body(s):
            frontier, visited, parent_l, layer = s
            cand = expand(frontier, visited)
            with jax.named_scope("bfs.exchange"):
                # row j of (D, v_loc) -> chip j; received rows = every
                # chip's candidates for MY vertex range
                cand = cand.reshape(n_devices, v_loc)
                mine = jax.lax.all_to_all(cand, axis_names, split_axis=0,
                                          concat_axis=0, tiled=True)
                merged_l = mine.reshape(n_devices, v_loc).min(axis=0)
                newly_l = (merged_l < inf) & (parent_l == inf)
                parent_l = jnp.where(newly_l, merged_l, parent_l)
                # 32x-compressed frontier broadcast (the paper's bitmap
                # compression is what makes this cheap)
                new_frontier = jax.lax.all_gather(
                    bm.pack_bool(newly_l), axis_names,
                    tiled=True).reshape(w_cap)
                return (new_frontier, visited | new_frontier, parent_l,
                        layer + 1)

        _, _, parent_l, layer = loop(
            body, (frontier, visited, parent_l, jnp.int32(0)))
        return parent_l, layer

    return program


def sharded_program(mesh, axis_names: tuple[str, ...], v_loc: int,
                    n_vertices: int, max_layers: int, merge: str,
                    single_layer: bool = False):
    """`make_bfs_program` under ``shard_map`` over ``mesh``: takes the
    (D, e_loc) rows and owners sharded on ``axis_names`` and a
    replicated root; returns the (v_cap,) parent (sharded with
    merge="owner", else replicated) and the layer count."""
    n_devices = int(np.prod([mesh.shape[a] for a in axis_names]))
    program = make_bfs_program(v_loc, n_vertices, n_devices, axis_names,
                               max_layers, merge=merge,
                               single_layer=single_layer)
    p_out = P(axis_names) if merge == "owner" else P()
    return jax.shard_map(
        program, mesh=mesh,
        in_specs=(P(axis_names), P(axis_names), P()),
        out_specs=(p_out, P()))


def exchange_bytes(merge: str, v_cap: int, n_devices: int,
                   layers: int) -> int:
    """Bytes all chips together put on the wire in one search of
    ``layers`` layers (ring collectives: an all-reduce of N bytes sends
    2 N (D-1)/D per chip, an all-gather of N bytes from each chip
    N (D-1), an all-to-all of N bytes N (D-1)/D)."""
    d, bitmap = n_devices, v_cap // 8   # bytes of a V-bit bitmap
    if merge == "allreduce":
        per_chip = layers * 2 * 4 * v_cap * (d - 1) / d
    elif merge == "packed":
        per_chip = layers * bitmap * (d - 1) + 2 * 4 * v_cap * (d - 1) / d
    elif merge == "owner":
        per_chip = layers * (4 * v_cap * (d - 1) / d
                             + bitmap // d * (d - 1))
    else:
        raise ValueError(f"unknown merge {merge!r}")
    return int(round(per_chip * d))


# ---------------------------------------------------------------------------
# Mesh-facing wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("mesh", "axis_names", "v_loc",
                                             "n_vertices", "max_layers",
                                             "merge"))
def _search(rows_sh, owners_sh, root, *, mesh, axis_names, v_loc,
            n_vertices, max_layers, merge) -> engine.EngineResult:
    parent, layers = sharded_program(mesh, axis_names, v_loc, n_vertices,
                                     max_layers, merge)(rows_sh, owners_sh,
                                                        root)
    w_cap = parent.shape[0] // bm.BITS_PER_WORD
    state = engine.BfsState(jnp.zeros((1, w_cap), jnp.uint32),
                            bm.pack_bool(parent < n_vertices)[None],
                            parent[None], layers)
    return engine.EngineResult(state, layers[None], None)


def _search_call(part: MeshPartition, root, max_layers: int, merge: str):
    return ((part.rows, part.owners, jnp.asarray(root, jnp.int32)),
            dict(mesh=part.mesh, axis_names=part.axis_names,
                 v_loc=part.v_loc, n_vertices=part.n_vertices,
                 max_layers=max_layers, merge=merge))


def run(part: MeshPartition, root, max_layers: int,
        merge: str) -> engine.EngineResult:
    """One search from ``root`` over a partition, in one launch: an
    `engine.EngineResult` of batch 1 whose ``state.parent`` (1, v_cap)
    follows the internal convention (INF == V unreached),
    ``state.visited`` is its reached bitmap, the frontier is empty and
    ``depths`` counts the layers the search ran (the reference depth
    + 1 for a whole search, ``max_layers`` for one cut short).  The
    mesh program keeps no per-layer stats: ``stats`` is None."""
    args, kwargs = _search_call(part, root, max_layers, merge)
    return _search(*args, **kwargs)


def lower(part: MeshPartition, root, max_layers: int, merge: str):
    """``jax.jit(...).lower`` of the program `run` launches."""
    args, kwargs = _search_call(part, root, max_layers, merge)
    return _search.lower(*args, **kwargs)


def run_bfs_distributed(csr: Csr, root: int, mesh,
                        axis_names: tuple[str, ...] | None = None,
                        max_layers: int | None = None,
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
    merge="owner" each chip keeps only its P slice during the search;
    the concatenated result is identical.
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
    part = partition_on_mesh(csr, mesh, axis_names)
    res = run(part, root, max_layers, merge)
    return res.state.parent[0, :csr.n_vertices], res.depths[0]
