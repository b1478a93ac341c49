"""Graph-format protocol — the paper's §4.2 layout axis made pluggable.

§4.2 spends a full section on data alignment and padding so the Xeon
Phi's gathers never fall into peel/remainder loops; our CSR mimics
that with 128-lane sentinel padding (core/csr.py).  SlimSell
[Besta et al., arXiv:2010.09913] shows the *layout itself* is a free
variable: a sliced-ELLPACK (SELL-C-σ) adjacency is strictly better
suited to wide-SIMD BFS on skewed-degree graphs, and the hybrid
follow-up [Paredes et al., arXiv:1704.02259] notes the bottom-up
phase wants a different layout than top-down.

`GraphFormat` is the contract the traversal engine consumes:

* **build**     — ``from_edges`` / ``from_graph`` (preprocess-on-load;
  Graph500 kernel-2 territory, untimed in the benchmark).
* **gather**    — ``make_steps`` returns the batched per-layer step
  for each engine mode (scalar / SIMD-kernel / bottom-up), the
  format-specialized replacement for the raw ``colstarts/rows``
  apportionment.  All steps share one signature
  ``(frontier, visited, parent) -> (out, visited, parent, StepAux)``
  with a leading root axis, so direction policies work unmodified;
  the `engine.StepAux` tail carries the step's active-tile and
  truncation counters.  The ``pipeline`` build flag selects between
  the frontier-proportional **fused_gather** steps (ISSUE 3:
  in-kernel gather + scalar-prefetched active-tile work-lists) and
  the legacy **materialized** full-stream steps (the ablation
  baseline).
* **counters**  — ``degrees`` feeds the engine's on-device Table 1
  workload counters; ``edge_slots``/``layer_bytes``/``tile_bytes``/
  ``plan_bytes`` are the format's per-layer stream-width and
  bytes-moved accounting for both pipelines (`traversal_bytes` sums
  them over a traversal's layer stats).
* **footprint** — ``footprint`` reports device bytes per array so the
  autotuner and benchmarks can compare layouts.

Formats are registered JAX pytrees (arrays as leaves, static shape
metadata as aux data), so a format instance can be passed straight
into the jitted fused engine (`engine.traverse_format`).
"""
from __future__ import annotations

import abc
from typing import ClassVar, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.csr import Csr, padded_vertex_count, \
    padding_premarked_visited
from repro.core.rmat import EdgeList


class Footprint(NamedTuple):
    """Device-memory report for one built format."""
    format: str
    arrays: tuple[tuple[str, int], ...]   # (array name, bytes)

    @property
    def total_bytes(self) -> int:
        return sum(b for _, b in self.arrays)

    def summary(self) -> str:
        parts = ", ".join(f"{n}={b/2**20:.2f}MiB" for n, b in self.arrays)
        return (f"{self.format}: {self.total_bytes/2**20:.2f} MiB "
                f"({parts})")


def nbytes(arr: jax.Array) -> int:
    return int(arr.size) * arr.dtype.itemsize


def csr_to_edges(csr: Csr) -> EdgeList:
    """Recover the (sorted, symmetrized) COO edge list from a CSR.

    Sentinel padding lives at the tail of ``rows``, so the first
    ``n_edges`` entries are exactly the real destination list.
    """
    src = jnp.repeat(jnp.arange(csr.n_vertices, dtype=jnp.int32),
                     csr.degrees(),
                     total_repeat_length=csr.n_edges_padded)
    return EdgeList(src=src[:csr.n_edges],
                    dst=csr.rows[:csr.n_edges],
                    n_vertices=csr.n_vertices)


class GraphFormat(abc.ABC):
    """Abstract adjacency layout consumed by the traversal engine.

    Subclasses are pytree-registered dataclass-likes: jax arrays in
    ``tree_flatten`` leaves, static ints (vertex/edge counts, slice
    geometry) in aux data — which is what lets `engine.traverse_format`
    jit over a format instance directly.
    """

    name: ClassVar[str]

    #: whether the layout streams edge tiles an input-DMA pipeline can
    #: run ahead of (``TraversalSpec.prefetch_depth > 0``); formats
    #: with no streamed input (the bitmap word sweep) set this False
    #: and `spec.validate(fmt)` rejects the combination
    supports_prefetch: ClassVar[bool] = True

    #: whether the layout implements the whole-layer megakernel
    #: (``TraversalSpec.pipeline="megakernel"`` — ISSUE 6: plan +
    #: compact + gather-expand + restoration in ONE Pallas call).
    #: Opt-in: the format must build megakernel steps in
    #: `_build_steps`; `spec.validate(fmt)` rejects the pipeline on
    #: formats that don't (bitmap has no per-layer launches to fuse).
    #: Since ISSUE 9 both streamed layouts fuse: CSR via the rows-block
    #: schedule, SELL via manual `make_async_copy` cols DMA consuming
    #: an in-kernel slab work-list (kernels/sell_expand.py)
    supports_megakernel: ClassVar[bool] = False

    #: whether the layout implements the whole-TRAVERSAL persistent
    #: kernel (``TraversalSpec.pipeline="persistent"`` — ISSUE 9: the
    #: layer loop, direction decision and termination run INSIDE one
    #: Pallas launch, frontier/visited/parents VMEM-resident across
    #: layers).  Opt-in via `persistent_run`/`persistent_fits`;
    #: `spec.validate(fmt)` rejects the pipeline on formats that don't
    supports_persistent: ClassVar[bool] = False

    #: scalar algorithms the persistent kernel can honor — the
    #: in-kernel layer loop has no plain-jnp scalar arm, so a format
    #: whose MODE_SCALAR semantics differ per algorithm (SELL's
    #: "nonsimd" dense sweep) restricts the set and `spec.validate`
    #: rejects the rest
    persistent_algorithms: ClassVar[tuple] = ()

    #: semiring `TraversalSpec.algorithm` values this layout can run
    #: (ISSUE 10: "sssp" / "cc" / "ksource_bfs").  Opt-in via
    #: `_build_semiring_step`: the layout must offer a per-layer
    #: relaxation step (the scatter-min kernels) — the bitmap word
    #: sweep stores no per-edge stream to relax over and keeps the
    #: empty default, which `spec.validate(fmt)` turns into a typed
    #: rejection instead of a silent wrong answer
    supported_semirings: ClassVar[tuple] = ()

    #: pipelines whose steps the TPU compiler accepts for this layout.
    #: On a TPU backend `TraversalSpec.resolve` picks ``auto`` from
    #: these only, and `spec.validate(fmt)` turns a request for any
    #: other pipeline into a `KernelRefusedError` that quotes its
    #: refusals (`kernels.TPU_REFUSALS`)
    tpu_pipelines: ClassVar[tuple] = ()

    # -- construction ----------------------------------------------------
    @classmethod
    @abc.abstractmethod
    def from_edges(cls, edges: EdgeList, **kwargs) -> "GraphFormat":
        """Build the layout from a COO edge list (preprocess-on-load)."""

    @classmethod
    def from_graph(cls, graph, **kwargs) -> "GraphFormat":
        """Build from whatever the caller holds: EdgeList, Csr, an
        already-built format of this class (passthrough), or a built
        format that can recover its CSR (``to_csr``)."""
        if isinstance(graph, cls):
            return graph
        if isinstance(graph, GraphFormat):
            to_csr = getattr(graph, "to_csr", None)
            if to_csr is None:
                raise TypeError(
                    f"cannot re-lay-out a built {type(graph).__name__} "
                    f"as {cls.__name__}; pass the Csr or EdgeList it "
                    f"was built from")
            graph = to_csr()
        if isinstance(graph, Csr):
            from_csr = getattr(cls, "from_csr", None)
            if from_csr is not None:     # skip the edge-list round trip
                return from_csr(graph, **kwargs)
            return cls.from_edges(csr_to_edges(graph), **kwargs)
        if isinstance(graph, EdgeList):
            return cls.from_edges(graph, **kwargs)
        raise TypeError(
            f"cannot build {cls.__name__} from {type(graph).__name__}")

    # -- static geometry -------------------------------------------------
    @property
    @abc.abstractmethod
    def n_vertices(self) -> int:
        """Real vertex count V (the sentinel id)."""

    @property
    @abc.abstractmethod
    def n_edges(self) -> int:
        """Real directed edge count (un-padded)."""

    @property
    def n_vertices_padded(self) -> int:
        """Vertex-array size — the engine-wide §4.2 padding convention."""
        return padded_vertex_count(self.n_vertices)

    @property
    def sentinel(self) -> int:
        return self.n_vertices

    # -- engine contract -------------------------------------------------
    @abc.abstractmethod
    def degrees(self) -> jax.Array:
        """(V,) int32 out-degrees — the Table 1 workload counter input."""

    def make_steps(self, spec=None, *, algorithm=None, tile=None,
                   pipeline=None, packed=None,
                   prefetch_depth=None) -> dict:
        """Batched per-layer steps keyed by engine mode.

        Since ISSUE 5 the configuration argument is ONE resolved
        `repro.api.spec.TraversalSpec` — validated here against this
        format (`spec.validate(fmt)`, the single home of invalid-combo
        rejection) and handed to the format's `_build_steps`.  The
        loose keyword form (``algorithm=/tile=/...``) is deprecated
        but still accepted: it is normalized into a spec (tile through
        `resolve_tile`) and follows the same path.

        Returns ``{MODE_SCALAR: fn, MODE_SIMD: fn, MODE_BOTTOMUP: fn}``
        where each ``fn(frontier, visited, parent)`` advances every
        root in the leading batch axis by one layer and returns
        ``(out, visited, parent, engine.StepAux)``.

        Spec fields a format may ignore: ``pipeline`` where one sweep
        serves both flavours (the bitmap layout); ``packed`` where
        planning is already word-native (SELL's membership test, the
        bitmap sweep); ``prefetch_depth`` is *rejected* (not ignored)
        where there is no streamed input to prefetch (bitmap).
        """
        if spec is None:
            # reuse the engine shims' single knob->spec normalizer so
            # the legacy defaults live in exactly one place
            # (engine._KNOB_DEFAULTS) — the defaults-drift class this
            # redesign exists to kill
            from repro.core.engine import _UNSET, _spec_from_knobs
            knobs = dict(algorithm=algorithm, tile=tile,
                         pipeline=pipeline, packed=packed,
                         prefetch_depth=prefetch_depth)
            spec = _spec_from_knobs(
                f"{type(self).__name__}.make_steps",
                None,
                {k: (_UNSET if v is None else v)
                 for k, v in knobs.items()}).resolve(self)
        elif not spec.is_resolved:
            autos = [f for f in spec.field_names()
                     if getattr(spec, f) == "auto"]
            why = (f"fields still 'auto': {autos}" if autos
                   else f"policy is the name {spec.policy!r}, not a "
                        f"policy object")
            raise ValueError(
                f"{type(self).__name__}.make_steps needs a *resolved* "
                f"TraversalSpec ({why}); call spec.resolve(fmt) — or "
                f"repro.bfs.plan, which resolves once and caches the "
                f"executable")
        else:
            spec.validate(self)
        return self._build_steps(spec)

    @abc.abstractmethod
    def _build_steps(self, spec) -> dict:
        """Format-owned step construction from a resolved, validated
        `TraversalSpec` (see `make_steps` for the contract)."""

    def make_semiring_step(self, spec, semiring):
        """One batched per-layer semiring relaxation step (ISSUE 10).

        ``spec`` must be resolved with ``spec.algorithm`` in this
        format's ``supported_semirings`` (`spec.validate(fmt)` is the
        one rejection home, as for `make_steps`); ``semiring`` is the
        registered `algorithms.semiring.Semiring` instance.  Returns
        ``fn(frontier, vals, dense) -> (new_vals, p_layer, StepAux)``
        where ``frontier`` is (B, W) packed words, ``vals`` the
        (B, V_pad) value rows, ``dense`` a (B,) bool selecting the
        full-work-list sweep (the CC endgame's dense arm), and
        ``p_layer`` the per-layer min-id parent scatter the driver
        merges under the improved mask.
        """
        spec.validate(self)
        return self._build_semiring_step(spec, semiring)

    def _build_semiring_step(self, spec, semiring):
        """Format-owned semiring step construction; formats that list
        nothing in ``supported_semirings`` never reach here (validate
        rejects first), so the default is a hard error."""
        raise NotImplementedError(
            f"{type(self).__name__} declares no supported_semirings")

    def resolve_tile(self, tile: int | None) -> int:
        """The format owns tile selection (§4.2: the layout fixes the
        aligned unit).  ``tile`` is the user's override where the
        format honors one; the default accepts any and returns 1."""
        return int(tile) if tile else 1

    # -- persistent (whole-traversal) contract (ISSUE 9) -----------------
    def persistent_fits(self, n_roots: int, spec) -> bool:
        """True when the whole-traversal persistent kernel's working
        set (the full batch's state, resident across layers) fits the
        VMEM budget for this geometry under the *resolved* ``spec``.
        The engine consults this at trace time and degrades
        ``pipeline="persistent"`` observably when False.  Formats
        without a persistent kernel never fit."""
        return False

    def persistent_run(self, frontier, visited, parent, spec):
        """Run the WHOLE multi-root traversal in ONE Pallas launch
        (``supports_persistent`` formats only): layer loop, §4.1
        direction decision and termination all in-kernel.  Arguments
        are the `engine._init_batched` state arrays; returns
        ``(frontier, visited, parent, depths, layers, stats)`` — the
        fused engine's whole-traversal contract, with the stats launch
        column charging 1 per *traversal* (at layer 0)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no whole-traversal persistent "
            f"kernel (supports_persistent=False)")

    # -- accounting ------------------------------------------------------
    @abc.abstractmethod
    def footprint(self) -> Footprint:
        """Per-array device bytes."""

    @property
    @abc.abstractmethod
    def edge_slots(self) -> int:
        """Edge-stream slots one SIMD layer examines (incl. padding)."""

    def layer_bytes(self) -> int:
        """Analytic bytes one *materialized* SIMD layer streams from
        HBM (the bytes-moved counter of benchmarks/bfs_formats.py).
        Default: the edge stream at 4 B/slot for the (nbr, cand,
        valid) triple; CSR overrides with the write+read round trip
        its pipeline actually performs."""
        return 3 * 4 * self.edge_slots

    # -- fused-pipeline accounting (ISSUE 3) -----------------------------
    def tile_bytes(self, tile: int) -> int:
        """Bytes ONE active tile DMAs in the fused pipeline — ``tile``
        is in the format's own grid units (CSR: rows slots; SELL:
        slabs per step)."""
        return 4 * tile

    def mask_bytes(self, packed: bool = True) -> int:
        """Per-layer frontier/visited/next *membership* bytes the
        engine holds/streams (ISSUE 4's packed-bytes model): packed
        uint32 words cost ``3 * V_pad / 8`` per layer; the legacy
        dense int32-mask representation cost ``3 * 4 * V_pad`` — the
        32x the paper's §3.3.1 compression buys."""
        w_bytes = self.n_vertices_padded // 8
        return 3 * w_bytes if packed else 3 * 4 * self.n_vertices_padded

    def plan_mask_bytes(self, packed: bool = True) -> int:
        """Bytes of active-set membership the planning pass reads per
        layer: the packed bitmap (V/8) vs the dense V-mask (4V)."""
        if packed:
            return self.n_vertices_padded // 8
        return 4 * self.n_vertices_padded

    def plan_bytes(self, tile: int, packed: bool = True) -> int:
        """Per-layer traffic of the fused pipeline's planning pass
        (the active-tile marking + work-list round trip) — charged
        once per layer regardless of frontier size, which is exactly
        why fused bytes stay ~flat on thin layers."""
        n_blocks = -(-self.edge_slots // max(tile, 1))
        return (self.plan_mask_bytes(packed)    # active mask read
                + 2 * 4 * n_blocks)             # work-list write+read

    # -- admission-time validation (ISSUE 8) ----------------------------
    def validate_structure(self) -> "GraphFormat":
        """Strict structural validation at admission time.

        Raises `repro.errors.GraphValidationError` when the built
        layout could produce a *wrong traversal* (out-of-range ids,
        non-monotone extents, NaN geometry).  The default covers the
        geometry scalars every format shares; layouts with checkable
        adjacency arrays override (CsrFormat routes through
        `core.csr.check_structure`).  Tracer-held arrays skip data
        checks.  Returns ``self`` so call sites can chain.
        """
        from repro.core.csr import _as_count
        from repro.errors import GraphValidationError
        v = _as_count("n_vertices", self.n_vertices)
        _as_count("n_edges", self.n_edges)
        if v < 1:
            raise GraphValidationError(
                "n_vertices must be >= 1 (a BFS needs at least a root "
                "vertex); got 0")
        return self

    # -- shared init helpers --------------------------------------------
    def init_visited(self) -> jax.Array:
        """Visited bitmap with every padding vertex pre-marked — the
        mask-replaces-remainder-loops convention of §4.2 (shared with
        the CSR drivers via `csr.padding_premarked_visited`)."""
        return padding_premarked_visited(self.n_vertices)

    def __repr__(self) -> str:
        return (f"{type(self).__name__}(V={self.n_vertices}, "
                f"E={self.n_edges})")


def traversal_bytes(fmt: GraphFormat, stats, *, tile: int,
                    pipeline: str = "fused_gather",
                    packed: bool = True) -> int:
    """Analytic HBM bytes a whole traversal's expansion layers moved.

    ``stats`` is `engine.layer_stats(result)` — the fused pipeline
    charges each layer its *measured* active tiles plus the planning
    pass; the materialized pipeline charges the full stream every
    layer.  Single-root accounting (batched stats sum tiles across
    roots, so the fused term scales; the materialized term would need
    an explicit root multiplier).  ``packed`` selects the planning
    pass's mask-byte model (packed words vs dense masks).
    """
    if pipeline == "materialized":
        return fmt.layer_bytes() * len(stats)
    return sum(fmt.tile_bytes(tile) * s.active_tiles
               + fmt.plan_bytes(tile, packed) for s in stats)


def membership_bytes(fmt: GraphFormat, stats, *,
                     packed: bool = True) -> int:
    """Analytic frontier/visited/next *membership* bytes a traversal
    carried per its representation (the ISSUE 4 acceptance counter):
    per layer, the three state bitmaps plus the planning pass's
    active-set read — V/8-scaled under ``packed``, 4V-scaled under
    the legacy dense-mask representation.

    Scope: this counts the representation-dependent DELTA only.  Both
    planning arms additionally materialize V-sized int32 working
    arrays (the packed arm's compacted queue and gathered colstarts
    ranges; the dense arm's per-vertex colstarts slices and block-id
    intermediates) — those are common to both and cancel, so they are
    deliberately excluded.  The live-state counterpart (measured from
    actual traversal arrays, immune to model drift) is checked by
    `benchmarks.check_bytes_regression`."""
    per_layer = fmt.mask_bytes(packed) + fmt.plan_mask_bytes(packed)
    return per_layer * len(stats)
