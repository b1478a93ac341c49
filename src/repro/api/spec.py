"""TraversalSpec — ONE declarative configuration object for every BFS.

The paper's whole point is that a single traversal algorithm has many
orthogonal tuning axes that must be co-selected per graph: the §4.1
layer-adaptive direction decision, the §4.2 aligned tile unit, the §4
vprefetch distance, the §3.3.1 packed-word representation.  Beamer et
al. [2012] and the Buluç–Madduri survey frame the same set as a single
*traversal configuration* chosen once per graph.  After PRs 1–4 this
repo exposed those axes as seven loose keyword knobs copy-threaded
through every entry point, each with its own ``static_argnames`` list
and drifting defaults; `TraversalSpec` is the one frozen object that
replaces the knob pile.

Every field also accepts ``"auto"``; autos are resolved exactly ONCE,
at plan time (`TraversalSpec.resolve`), against the graph's format —
and EVERY auto reads the committed ``BENCH_bfs.json`` table through
the ONE `formats.affinity.resolve` lookup (geometry-keyed
``affinity.{format}.{geometry}.{knob}{value}`` rows, regenerated by
``make bench-affinity``), falling back to the per-knob heuristic when
no row matches — so ``CompiledTraversal.resolved`` is always a
fully-concrete, loggable, hashable record of what actually ran.

Field → paper-knob map (the §-references are to the source paper):

* ``policy``          — the §4.1 layer-adaptive direction decision
  (which expansion flavour each layer runs).  A policy *object*
  (`engine.TopDown` / `ThresholdSimd` / `PaperLiteralLayers` /
  `BeamerHybrid`), a registered name string, or ``"auto"`` (degree
  skew >= `autotune.SKEW_THRESHOLD` picks the Beamer hybrid, else the
  edge-threshold SIMD switch).
* ``algorithm``       — which scalar expander backs MODE_SCALAR
  layers: ``"simd"`` (Algorithm 3: bitmaps + racy scatter +
  restoration §3.3.2) or ``"nonsimd"`` (Algorithm 2: exact dense
  updates).  Auto: ``"simd"``.
* ``pipeline``        — the expansion gather pipeline:
  ``"fused_gather"`` (in-kernel CSR gather + active-tile scheduling,
  HBM traffic proportional to the frontier, 3 Pallas calls/layer),
  ``"megakernel"`` (ISSUE 6: the whole layer — plan + compact +
  gather + restoration — as ONE Pallas call whose work-list never
  leaves SMEM/VMEM; requires `supports_megakernel`: CSR and, since
  ISSUE 9, SELL), ``"persistent"`` (ISSUE 9: the whole TRAVERSAL —
  layer loop, §4.1 direction decision, termination — as ONE Pallas
  launch with state VMEM-resident across layers; requires
  `supports_persistent` and an algorithm in the format's
  `persistent_algorithms`), ``"materialized"`` (the legacy full-E
  edge stream; the ablation baseline) or ``"xla"`` (the shared
  `expand_candidates` body as XLA ops over the whole edge stream, no
  Pallas kernel).  Auto: the affinity row, else ``"fused_gather"`` — and on
  a TPU backend only a pipeline in the format's `tpu_pipelines`
  (today ``"xla"``: the compiler refuses the other expansion
  kernels); an explicit refused pipeline there raises
  `errors.KernelRefusedError`.
* ``packed``          — §3.3.1's bitmap compression as the engine's
  native per-layer representation (SIMD compaction kernel, V/8 mask
  bytes per layer) vs the legacy dense-mask arm.  Auto: ``True``.
* ``tile``            — §4.2's aligned unit: the fused pipeline's DMA
  block and therefore its prefetch distance (format-defined units:
  CSR rows-slots, SELL slabs per grid step).  Auto: the format's
  `resolve_tile(None)` — for CSR the ``REPRO_BFS_TILE`` env override,
  else the geometry-keyed BENCH affinity row (flat PR-4 rows as the
  back-compat tier), else 1024.
* ``prefetch_depth``  — §4's ``vprefetch0/vprefetch1`` distance as an
  explicit knob: input-DMA tiles kept in flight ahead of the compute
  tile in the gather kernels (0 = the BlockSpec pipeline's automatic
  double buffering).  Auto: ``0``.  Invalid on the bitmap format,
  which streams no edge tiles.
* ``max_layers``      — static layer budget of the fused
  ``lax.while_loop`` (and the serve engine's per-query safety valve).
  Auto: ``64``.
* ``merge``           — the distributed per-layer exchange:
  ``"allreduce"`` (dense per-layer pmin), ``"owner"``
  (owner-computes all_to_all; parent output is the LOCAL slice) or
  ``"packed"`` (V/8-byte discovered-word all-gather + one post-loop
  pmin — bit-identical tree to allreduce).  Auto: ``"packed"``, the
  wire-optimal full-tree merge.  Ignored off-mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any

from repro.core import engine as _engine
from repro.kernels import interpret_mode

AUTO = "auto"

_ALGORITHMS = ("simd", "nonsimd")
_MERGES = ("allreduce", "owner", "packed")

#: semiring algorithm values (ISSUE 10) routed through the same plan
#: cache but executed by `algorithms.traversal.traverse_semiring`
#: instead of the hard-wired BFS engine.  The canonical tuple lives in
#: `algorithms.semiring`; re-exported here because this module is the
#: one home of algorithm-value validation.
from repro.algorithms.semiring import SEMIRING_ALGORITHMS  # noqa: E402

#: registered policy names <-> engine policy classes
POLICIES = {
    "topdown": _engine.TopDown,
    "threshold_simd": _engine.ThresholdSimd,
    "paper_layers": _engine.PaperLiteralLayers,
    "beamer": _engine.BeamerHybrid,
}
_POLICY_NAMES = {cls: name for name, cls in POLICIES.items()}


def _is_policy(obj: Any) -> bool:
    """Duck-typed DirectionPolicy: decides a mode from a Workload."""
    return callable(getattr(obj, "decide", None)) \
        and hasattr(obj, "modes")


def as_format(graph):
    """View whatever the caller holds as a built `GraphFormat`.

    Csr and EdgeList are wrapped as `CsrFormat` (no silent re-layout —
    picking a different layout is `formats.autotune.build`'s job);
    built formats pass through.
    """
    from repro.core.csr import Csr, from_edges
    from repro.core.rmat import EdgeList
    from repro.formats.base import GraphFormat
    from repro.formats.csr_format import CsrFormat
    if isinstance(graph, GraphFormat):
        return graph
    if isinstance(graph, Csr):
        return CsrFormat.from_csr(graph)
    if isinstance(graph, EdgeList):
        return CsrFormat.from_csr(from_edges(graph))
    raise TypeError(
        f"cannot plan a traversal over {type(graph).__name__}; expected "
        f"a Csr, EdgeList or built GraphFormat")


#: spec fields the distributed per-chip program (a fixed top-down
#: rowsweep) cannot honor — it consumes only merge/max_layers
MESH_IGNORED_FIELDS = ("policy", "algorithm", "pipeline", "packed",
                       "tile", "prefetch_depth")


def warn_mesh_ignored_fields(spec: "TraversalSpec", entry: str) -> None:
    """The ONE mesh-path contract warning (shared by
    `run_bfs_distributed` and mesh-bound `plan`): flag explicitly-set
    fields the fixed per-chip program ignores.  A fully-resolved spec
    passes silently — its concrete fields are resolution artifacts,
    not user intent."""
    if spec.is_resolved:
        return
    ignored = [f for f in MESH_IGNORED_FIELDS
               if getattr(spec, f) != AUTO]
    if ignored:
        import warnings
        warnings.warn(
            f"{entry}: the distributed per-chip program is a fixed "
            f"top-down rowsweep; spec fields {ignored} are ignored "
            f"(only merge/max_layers apply)",
            UserWarning, stacklevel=3)


@dataclasses.dataclass(frozen=True)
class TraversalSpec:
    """Frozen, hashable traversal configuration (see module docstring
    for the field → paper-knob map).  Every field accepts ``"auto"``;
    `resolve` turns autos into concrete values exactly once, and
    `validate` rejects invalid values/combinations in ONE place with
    actionable messages."""

    policy: Any = AUTO
    algorithm: str = AUTO
    pipeline: str = AUTO
    packed: Any = AUTO            # bool | "auto"
    tile: Any = AUTO              # positive int | "auto"
    prefetch_depth: Any = AUTO    # int >= 0 | "auto"
    max_layers: Any = AUTO        # int >= 1 | "auto"
    merge: str = AUTO

    # -- introspection ---------------------------------------------------
    @classmethod
    def field_names(cls) -> tuple[str, ...]:
        return tuple(f.name for f in dataclasses.fields(cls))

    @property
    def is_resolved(self) -> bool:
        """True iff no field is ``"auto"`` and policy is an object."""
        return (not any(getattr(self, f) == AUTO
                        for f in self.field_names())
                and _is_policy(self.policy))

    @property
    def is_semiring(self) -> bool:
        """True iff this spec selects the semiring portfolio (ISSUE
        10: sssp/cc/ksource_bfs) rather than the BFS engine."""
        return self.algorithm in SEMIRING_ALGORITHMS

    def replace(self, **changes) -> "TraversalSpec":
        return dataclasses.replace(self, **changes)

    # -- validation (the ONE home of combination checks) -----------------
    def validate(self, fmt=None) -> "TraversalSpec":
        """Reject invalid values and invalid (spec, format) combos.

        Called standalone it checks every non-``"auto"`` field value;
        with ``fmt`` it additionally rejects combinations the format
        cannot honor (e.g. ``prefetch_depth > 0`` on the bitmap
        layout).  Returns self so call sites can chain."""
        p = self.policy
        if not (_is_policy(p) or p == AUTO or
                (isinstance(p, str) and p in POLICIES)):
            raise ValueError(
                f"unknown policy {p!r}; expected a DirectionPolicy "
                f"object, one of {sorted(POLICIES)}, or 'auto'")
        if self.algorithm != AUTO \
                and self.algorithm not in _ALGORITHMS \
                and self.algorithm not in SEMIRING_ALGORITHMS:
            raise ValueError(
                f"unknown algorithm {self.algorithm!r}; expected a "
                f"scalar algorithm in {_ALGORITHMS}, a semiring "
                f"algorithm in {SEMIRING_ALGORITHMS}, or 'auto'")
        if self.is_semiring:
            # the portfolio runs on the scatter-min relax kernels
            # (fused_gather arm only): the BFS-specific fused
            # pipelines and the software-prefetch stream have no
            # semiring variant, so an EXPLICIT request is a typed
            # error here (an affinity-table auto choice degrades
            # observably in `resolve` instead)
            if self.pipeline in ("megakernel", "persistent",
                                 "materialized"):
                raise ValueError(
                    f"pipeline={self.pipeline!r} is invalid for the "
                    f"semiring algorithm {self.algorithm!r}: the "
                    f"portfolio kernels only implement the "
                    f"'fused_gather' relax arm — use "
                    f"pipeline='fused_gather' (or 'auto')")
            if isinstance(self.prefetch_depth, int) \
                    and self.prefetch_depth > 0:
                raise ValueError(
                    f"prefetch_depth={self.prefetch_depth} is invalid "
                    f"for the semiring algorithm {self.algorithm!r}: "
                    f"the relax kernels have no manual prefetch "
                    f"stream — use prefetch_depth=0 (or 'auto')")
        if self.pipeline != AUTO:
            _engine.check_pipeline(self.pipeline)
        if self.merge != AUTO and self.merge not in _MERGES:
            raise ValueError(
                f"unknown merge {self.merge!r}; expected one of "
                f"{_MERGES} or 'auto' (merge only matters with a mesh)")
        if self.packed != AUTO and not isinstance(self.packed, bool):
            raise ValueError(
                f"packed must be True, False or 'auto', got "
                f"{self.packed!r}")
        if self.tile != AUTO and (not isinstance(self.tile, int)
                                  or isinstance(self.tile, bool)
                                  or self.tile < 1):
            raise ValueError(
                f"tile must be a positive int or 'auto', got "
                f"{self.tile!r}")
        if self.prefetch_depth != AUTO and (
                not isinstance(self.prefetch_depth, int)
                or isinstance(self.prefetch_depth, bool)
                or self.prefetch_depth < 0):
            raise ValueError(
                f"prefetch_depth must be an int >= 0 or 'auto', got "
                f"{self.prefetch_depth!r}")
        if self.max_layers != AUTO and (
                not isinstance(self.max_layers, int)
                or isinstance(self.max_layers, bool)
                or self.max_layers < 1):
            raise ValueError(
                f"max_layers must be an int >= 1 or 'auto', got "
                f"{self.max_layers!r}")
        if fmt is not None:
            fmt_label = getattr(fmt, "name", type(fmt).__name__)
            if self.is_semiring:
                allowed = getattr(fmt, "supported_semirings", ())
                if self.algorithm not in allowed:
                    raise ValueError(
                        f"algorithm={self.algorithm!r} is invalid for "
                        f"the {fmt_label!r} format: it declares "
                        f"supported_semirings={allowed!r} — pick a "
                        f"layout with a per-edge candidate stream "
                        f"like 'csr'/'sell'")
            depth = self.prefetch_depth
            if isinstance(depth, int) and depth > 0 \
                    and not getattr(fmt, "supports_prefetch", True):
                raise ValueError(
                    f"prefetch_depth={depth} is invalid for the "
                    f"{fmt_label!r} "
                    f"format: it streams no edge tiles to prefetch "
                    f"(supports_prefetch=False) — use prefetch_depth=0 "
                    f"(or 'auto'), or pick a streamed layout like "
                    f"'csr'/'sell'")
            if self.pipeline == "megakernel" \
                    and not getattr(fmt, "supports_megakernel", True):
                raise ValueError(
                    f"pipeline='megakernel' is invalid for the "
                    f"{fmt_label!r} format: it has no whole-layer "
                    f"fused kernel (supports_megakernel=False) — use "
                    f"pipeline='fused_gather' (or 'auto'), or pick a "
                    f"layout with a megakernel like 'csr'")
            if self.pipeline == "persistent":
                if not getattr(fmt, "supports_persistent", False):
                    raise ValueError(
                        f"pipeline='persistent' is invalid for the "
                        f"{fmt_label!r} format: it has no "
                        f"whole-traversal fused kernel "
                        f"(supports_persistent=False) — use "
                        f"pipeline='megakernel'/'fused_gather' (or "
                        f"'auto'), or pick a layout with a persistent "
                        f"kernel like 'csr'/'sell'")
                allowed = getattr(fmt, "persistent_algorithms", ())
                if self.algorithm != AUTO and allowed \
                        and self.algorithm not in allowed:
                    raise ValueError(
                        f"pipeline='persistent' on the {fmt_label!r} "
                        f"format honors algorithm in {allowed}, got "
                        f"{self.algorithm!r}: the in-kernel layer "
                        f"loop has no plain-jnp scalar arm — use one "
                        f"of {allowed}, or pipeline='megakernel'")
            if self.pipeline == "xla" and isinstance(depth, int) \
                    and depth > 0:
                raise ValueError(
                    f"prefetch_depth={depth} is invalid with "
                    f"pipeline='xla': the XLA expansion streams no "
                    f"tiles to prefetch — use prefetch_depth=0 (or "
                    f"'auto')")
            if not interpret_mode():
                self._check_tpu(fmt, fmt_label)
        return self

    def _check_tpu(self, fmt, fmt_label: str) -> None:
        """On a TPU backend, reject a pipeline (or the semiring
        portfolio) whose kernels the compiler refuses, quoting the
        refusal — `resolve` never picks one for ``auto``."""
        from repro.errors import KernelRefusedError
        from repro.kernels import TPU_REFUSALS
        allowed = getattr(fmt, "tpu_pipelines", ())
        if self.is_semiring:
            what, key = f"algorithm={self.algorithm!r}", "semiring"
        elif self.pipeline == AUTO or self.pipeline in allowed:
            return
        else:
            what, key = f"pipeline={self.pipeline!r}", self.pipeline
        why = "; ".join(f"{k}: {r}"
                        for k, r in TPU_REFUSALS.get(key, {}).items())
        raise KernelRefusedError(
            f"{what} on the {fmt_label!r} format needs Pallas kernels "
            f"the TPU compiler refuses ({why or 'no TPU lowering'}) — "
            f"on a TPU the format compiles pipeline in {allowed} "
            f"with a BFS algorithm (or 'auto')")

    # -- auto resolution (exactly once, at plan time) --------------------
    def resolve(self, graph) -> "TraversalSpec":
        """Resolve every ``"auto"`` against the graph's format.

        EVERY auto field routes through ONE lookup mechanism —
        `formats.affinity.resolve` (ISSUE 6): env override >
        geometry-keyed committed ``BENCH_bfs.json`` rows (per format
        and density/skew class) > the PR-4 flat ``affinity.tile*``
        rows (back-compat) > the field's built-in default.
        Deterministic given the graph and the committed table.  The
        returned spec `is_resolved` and has been validated against
        the format.  Requires a concrete graph when ``policy="auto"``
        (the degree statistics must be readable); for the other autos
        a tracer-held format (e.g. inside a jitted legacy shim) skips
        the geometry tier unless its geometry was classified
        concretely before (the class is memoized per geometry).
        """
        self.validate()
        fmt = as_format(graph)
        from repro.formats import affinity

        def auto(knob, default):
            return affinity.resolve(fmt, knob, default)

        policy = self.policy
        if policy == AUTO:
            name = auto("policy", None)
            if isinstance(name, str) and name in POLICIES:
                policy = POLICIES[name]()
            else:
                from repro.formats import autotune
                s = autotune.measure(fmt)
                policy = (_engine.BeamerHybrid()
                          if s.degree_skew >= autotune.SKEW_THRESHOLD
                          else _engine.ThresholdSimd())
        elif isinstance(policy, str):
            policy = POLICIES[policy]()
        # the tile auto stays owned by the format's resolve_tile rule
        # (floors/caps are layout facts) — the rule itself reads the
        # affinity table through the same `affinity.resolve` path
        tile = fmt.resolve_tile(None if self.tile == AUTO else self.tile)
        algorithm = (auto("algorithm", "simd")
                     if self.algorithm == AUTO else self.algorithm)
        pipeline = self.pipeline
        if pipeline == AUTO:
            pipeline = auto("pipeline", "fused_gather")
            tpu = getattr(fmt, "tpu_pipelines", ())
            if not interpret_mode() and not self.is_semiring and tpu \
                    and pipeline not in tpu:
                # the affinity rows time kernels a TPU does not
                # compile: there the candidates are the declared ones
                pipeline = tpu[0]
            if pipeline == "persistent":
                allowed = getattr(fmt, "persistent_algorithms", ())
                ok = getattr(fmt, "supports_persistent", False) \
                    and (not allowed or algorithm in allowed)
                if not ok:
                    # an affinity row tuned elsewhere must not force
                    # an unsupported whole-traversal kernel —
                    # downgrade observably to the next-fused pipeline
                    from repro.obs.metrics import record_degrade
                    fmt_label = getattr(fmt, "name",
                                        type(fmt).__name__)
                    record_degrade(
                        "pipeline_unsupported",
                        reason=(f"{fmt_label!r} cannot honor the "
                                f"affinity table's "
                                f"pipeline='persistent' (supports_"
                                f"persistent="
                                f"{getattr(fmt, 'supports_persistent', False)}, "
                                f"algorithm={algorithm!r} vs "
                                f"{allowed})"),
                        fallback="megakernel/fused_gather per-layer "
                                 "steps")
                    pipeline = ("megakernel"
                                if getattr(fmt, "supports_megakernel",
                                           False)
                                else "fused_gather")
            if pipeline == "megakernel" \
                    and not getattr(fmt, "supports_megakernel", True):
                # a table row tuned on another format's geometry class
                # must not force an unsupported pipeline on this one —
                # downgrade, but observably (serve.degrade counter +
                # warn-once log)
                from repro.obs.metrics import record_degrade
                fmt_label = getattr(fmt, "name", type(fmt).__name__)
                record_degrade(
                    "pipeline_unsupported",
                    reason=(f"{fmt_label!r} has no whole-layer "
                            f"megakernel (supports_megakernel=False) "
                            f"but the affinity table selected "
                            f"pipeline='megakernel'"),
                    fallback="pipeline='fused_gather' unfused steps")
                pipeline = "fused_gather"
        if self.is_semiring and pipeline != "fused_gather":
            # an affinity row tuned for the BFS fused pipelines must
            # not silently select a kernel the portfolio doesn't have
            # — degrade observably to the relax arm's only pipeline
            from repro.obs.metrics import record_degrade
            record_degrade(
                "pipeline_unsupported",
                reason=(f"semiring algorithm {algorithm!r} has no "
                        f"{pipeline!r} kernel (the portfolio only "
                        f"implements the 'fused_gather' relax arm) "
                        f"but the affinity table selected it"),
                fallback="pipeline='fused_gather' relax steps")
            pipeline = "fused_gather"
        depth = self.prefetch_depth
        if depth == AUTO:
            depth = int(auto("prefetch_depth", 0))
            if depth > 0 and (pipeline == "xla" or not getattr(
                    fmt, "supports_prefetch", True)):
                depth = 0
            if depth > 0 and self.is_semiring:
                # same story as the pipeline: no prefetch stream in
                # the relax kernels — degrade the table's choice
                from repro.obs.metrics import record_degrade
                record_degrade(
                    "prefetch_unsupported",
                    reason=(f"semiring algorithm {algorithm!r} has no "
                            f"manual prefetch stream but the affinity "
                            f"table selected prefetch_depth={depth}"),
                    fallback="prefetch_depth=0 automatic pipelining")
                depth = 0
        resolved = self.replace(
            policy=policy,
            algorithm=algorithm,
            pipeline=pipeline,
            packed=(bool(auto("packed", True))
                    if self.packed == AUTO else self.packed),
            tile=int(tile),
            prefetch_depth=depth,
            max_layers=(int(auto("max_layers", 64))
                        if self.max_layers == AUTO else self.max_layers),
            merge=(auto("merge", "packed")
                   if self.merge == AUTO else self.merge))
        return resolved.validate(fmt)

    # -- serialization ---------------------------------------------------
    def to_dict(self) -> dict:
        """JSON-ready dict; policy objects serialize as
        ``{"name": ..., "params": {...}}`` (tuples become lists)."""
        d = {f: getattr(self, f) for f in self.field_names()}
        p = self.policy
        if _is_policy(p):
            cls = type(p)
            if cls not in _POLICY_NAMES:
                raise ValueError(
                    f"cannot serialize unregistered policy class "
                    f"{cls.__name__}; register it in spec.POLICIES")
            params = {k: (list(v) if isinstance(v, tuple) else v)
                      for k, v in dataclasses.asdict(p).items()}
            d["policy"] = {"name": _POLICY_NAMES[cls], "params": params}
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "TraversalSpec":
        """Inverse of `to_dict` (round-trips to an equal spec)."""
        unknown = set(d) - set(cls.field_names())
        if unknown:
            raise ValueError(
                f"unknown TraversalSpec fields {sorted(unknown)}; "
                f"expected a subset of {cls.field_names()}")
        kw = dict(d)
        p = kw.get("policy")
        if isinstance(p, dict):
            pol_cls = POLICIES[p["name"]]
            params = {k: (tuple(v) if isinstance(v, list) else v)
                      for k, v in p.get("params", {}).items()}
            kw["policy"] = pol_cls(**params)
        return cls(**kw).validate()
