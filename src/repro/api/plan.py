"""plan/compile/run — one cached jit executable behind every BFS.

The ROADMAP north-star (production-scale serving) wants the Graph500
shape of work: configure ONCE per graph, compile ONCE, then run many
roots without re-tracing or re-deciding knobs.  `plan` is that step:

    import repro.bfs as bfs
    ct = bfs.plan(graph, spec=bfs.TraversalSpec(policy="beamer"))
    res = ct.run(17)                    # single root
    res = ct.run_batched([3, 7, 11])    # leading root axis
    ct.resolved                         # the fully-concrete spec

``plan`` resolves the spec's ``"auto"`` fields exactly once
(`TraversalSpec.resolve` — the committed BENCH affinity table feeds
the tile auto, the autotune degree statistics feed the policy auto)
and returns a `CompiledTraversal` whose ``run`` / ``run_batched`` /
``layer_step`` all hit ONE cached jit executable keyed by
``(format class, geometry, resolved spec)``.  Planning the same
geometry + spec again — from any entry point, including every legacy
``traverse*`` shim — reuses the cached executable, so the engine
traces at most once per configuration regardless of how many surfaces
route through it (`_Executable.traces` is the probe the plan-cache
tests and the ``bfs_plan_cache`` micro-benchmark read).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.spec import TraversalSpec, as_format
from repro.core import engine as _engine
from repro.errors import GraphValidationError


def check_roots(roots, n_vertices: int) -> None:
    """Admission-time root validation (ISSUE 8): every root must be an
    integer in ``[0, n_vertices)``.  Raises `GraphValidationError`
    (IS-A ``ValueError``) — an out-of-range root would silently index
    the sentinel/padding region and return a wrong tree.  Tracer-held
    roots (inside a jitted caller) skip the check."""
    try:
        arr = np.asarray(roots)
    except Exception:
        return
    if arr.dtype.kind == "f":
        if np.any(~np.isfinite(arr)) or np.any(arr != np.floor(arr)):
            raise GraphValidationError(
                f"roots must be integers in [0, {n_vertices}), got "
                f"non-integral/NaN values {arr!r}")
    elif arr.dtype.kind not in "iu":
        raise GraphValidationError(
            f"roots must be integers in [0, {n_vertices}), got dtype "
            f"{arr.dtype}")
    if arr.size and (int(arr.min()) < 0
                     or int(arr.max()) >= n_vertices):
        bad = int(arr.min()) if int(arr.min()) < 0 else int(arr.max())
        raise GraphValidationError(
            f"root {bad} is outside [0, n_vertices={n_vertices}); "
            f"roots index real vertices (the sentinel/padding region "
            f"would return a wrong tree, not an error)")


def geometry_key(fmt) -> tuple:
    """Hashable (format class, static aux, leaf shapes/dtypes) key —
    what "same geometry" means for the plan cache.  Works on traced
    leaves too (shape/dtype are trace-time constants)."""
    leaves, aux = fmt.tree_flatten()
    return (type(fmt).__name__, aux,
            tuple((tuple(x.shape), str(x.dtype)) for x in leaves))


class _Executable:
    """The cached compile unit: one jitted whole-search program + one
    jitted single-layer tick for a (geometry, resolved spec) pair.
    ``traces`` counts engine traces (bumped at trace time only) — the
    probe behind the "≤1 trace per N runs" acceptance gate."""

    def __init__(self, spec: TraversalSpec):
        self.spec = spec
        self.traces = 0
        self.layer_traces = 0

        def _run(fmt, roots):
            self.traces += 1          # trace-time side effect only
            if spec.is_semiring:
                from repro.algorithms.traversal import traverse_semiring
                return traverse_semiring(fmt, roots, spec)
            return _engine._traverse_impl(fmt, roots, spec)

        def _layer(fmt, frontier, visited, parent):
            self.layer_traces += 1
            if spec.is_semiring:
                raise NotImplementedError(
                    f"semiring algorithm {spec.algorithm!r} has no "
                    f"single-layer tick: the portfolio driver owns "
                    f"the value/frontier carry — use run()/"
                    f"run_batched() for whole traversals")
            steps = fmt.make_steps(spec)
            mode = (_engine.MODE_SIMD if spec.algorithm == "simd"
                    else _engine.MODE_SCALAR)
            return steps[mode](frontier, visited, parent)[:3]

        self.run_jit = jax.jit(_run)
        self.layer_jit = jax.jit(_layer)


_CACHE: dict[tuple, _Executable] = {}
_STATS = {"hits": 0, "misses": 0}


def _executable(fmt, spec: TraversalSpec) -> _Executable:
    # ``merge`` is only read by the mesh path (which bypasses the
    # executable entirely) — normalize it out of the key so two specs
    # differing only in merge flavour share one single-chip trace
    key = (geometry_key(fmt), spec.replace(merge="auto"))
    ex = _CACHE.get(key)
    if ex is None:
        _STATS["misses"] += 1
        ex = _CACHE[key] = _Executable(spec)
    else:
        _STATS["hits"] += 1
    return ex


def cache_info() -> dict:
    """Plan-cache counters: {size, hits, misses}."""
    return {"size": len(_CACHE), **_STATS}


def clear_cache() -> None:
    """Drop every cached executable (tests / benchmarks)."""
    _CACHE.clear()
    _STATS.update(hits=0, misses=0)


class CompiledTraversal:
    """A graph bound to a fully-resolved `TraversalSpec` and its
    cached executable.

    Attributes:
      resolved: the concrete spec (every ``"auto"`` resolved) — the
        loggable/reproducible record of what runs.
      executable: the shared `_Executable` (identical across plans of
        equal geometry + spec — the cache identity tests assert on
        ``is``).
    """

    def __init__(self, fmt, resolved: TraversalSpec,
                 executable: _Executable | None, *,
                 batch: int | None = None, mesh: Any = None):
        self.fmt = fmt
        self.resolved = resolved
        self.executable = executable      # None iff mesh-bound
        self.batch = batch
        self.mesh = mesh
        # mesh path: the CSR partitioned over the mesh, built once here
        self._partition = (None if mesh is None
                           else _mesh_partition(fmt, mesh))

    # -- execution -------------------------------------------------------
    def run(self, roots) -> _engine.EngineResult:
        """Run for one root (int — unbatched result arrays) or a
        sequence of roots (leading root axis), `engine.traverse`
        semantics.  On a mesh-bound plan, runs the distributed program
        for one scalar root instead and returns its ``(parent,
        layers)`` pair."""
        if self.mesh is not None:
            check_roots(roots, self.fmt.n_vertices)
            if jnp.ndim(roots) != 0:
                raise ValueError("the distributed program runs one root "
                                 "per launch; pass a scalar root")
            res = self._search(roots)
            return res.state.parent[0, :self.fmt.n_vertices], res.depths[0]
        single = jnp.ndim(roots) == 0
        res = self.run_batched(
            jnp.atleast_1d(jnp.asarray(roots, jnp.int32)))
        if single:
            st = res.state
            return _engine.EngineResult(
                _engine.BfsState(st.frontier[0], st.visited[0],
                                 st.parent[0], st.layer),
                res.depths[0], res.stats,
                None if res.values is None else res.values[0])
        return res

    def run_batched(self, roots) -> _engine.EngineResult:
        """Run a (B,) root batch in one launch.  A plan built with
        ``batch=N`` pads smaller batches up to N (repeating the last
        root) and slices results back, so every batch size <= N hits
        the same trace.  NB the ``stats`` buffer is summed over the
        *padded* batch on device (the duplicate roots' work included)
        — for exact Table 1 accounting use an exact-width plan
        (``batch=None``).

        On a mesh-bound plan each root runs in a launch of its own
        (`bfs_distributed.run`) and the results are stacked: parents
        (B, v_cap), ``depths`` the layers each search ran, no stats.
        The layer counts are read back to count the exchange's wire
        bytes (``bfs.mesh.exchange_bytes``), so the call returns when
        the searches have ended."""
        if self.mesh is not None:
            return self._run_batched_distributed(roots)
        check_roots(roots, self.fmt.n_vertices)
        roots = jnp.atleast_1d(jnp.asarray(roots, jnp.int32))
        n = int(roots.shape[0])
        if n == 0:
            raise ValueError("run_batched needs at least one root")
        if self.batch is not None and n > self.batch:
            raise ValueError(
                f"root batch of {n} exceeds this plan's fixed "
                f"batch={self.batch}; chunk the roots or plan with a "
                f"larger batch (the fixed width is what guarantees "
                f"one trace)")
        if self.batch is not None and n < self.batch:
            pad = jnp.full((self.batch - n,), roots[-1], jnp.int32)
            res = self.executable.run_jit(
                self.fmt, jnp.concatenate([roots, pad]))
            st = res.state
            return _engine.EngineResult(
                _engine.BfsState(st.frontier[:n], st.visited[:n],
                                 st.parent[:n], st.layer),
                res.depths[:n], res.stats,
                None if res.values is None else res.values[:n])
        return self.executable.run_jit(self.fmt, roots)

    def layer_step(self, state, visited=None, parent=None):
        """Advance every root by exactly one layer (the serve tick).

        Accepts an `engine.BfsState` (returns a BfsState with layer+1)
        or the bare ``(frontier, visited, parent)`` triple (returns
        the updated triple)."""
        if self.mesh is not None:
            raise NotImplementedError(
                "mesh-bound plans have no single-layer tick; the "
                "distributed program runs whole searches via .run()")
        if visited is None:
            f, v, p = state.frontier, state.visited, state.parent
            nf, nv, np_ = self.executable.layer_jit(self.fmt, f, v, p)
            return _engine.BfsState(nf, nv, np_, state.layer + 1)
        return self.executable.layer_jit(self.fmt, state, visited,
                                         parent)

    def trace_run(self, roots, *, tracer=None, sync: bool = True,
                  profile_logdir: str | None = None):
        """Instrumented traversal: host-steps this plan's compiled
        ``layer_step`` recording per-layer wall-clock spans — the
        opt-in timing mode (`repro.obs.trace.trace_run`); the fused
        ``run`` fast path is untouched.  Returns a
        `repro.obs.trace.TraceRun`."""
        from repro.obs.trace import trace_run as _trace_run
        return _trace_run(self, roots, tracer=tracer, sync=sync,
                          profile_logdir=profile_logdir)

    def _search(self, root) -> _engine.EngineResult:
        from repro.core import bfs_distributed as dist
        return dist.run(self._partition, root, self.resolved.max_layers,
                        self.resolved.merge)

    def _run_batched_distributed(self, roots) -> _engine.EngineResult:
        from repro.core import bfs_distributed as dist
        from repro.obs.metrics import get_registry
        check_roots(roots, self.fmt.n_vertices)
        roots = np.atleast_1d(np.asarray(roots))
        if roots.shape[0] == 0:
            raise ValueError("run_batched needs at least one root")
        results = [self._search(r) for r in roots]
        part = self._partition
        get_registry().counter(
            "bfs.mesh.exchange_bytes",
            "bytes the mesh searches put on the wire").inc(sum(
                dist.exchange_bytes(self.resolved.merge, part.v_cap,
                                    part.n_devices,
                                    int(np.asarray(r.depths)[0]))
                for r in results))
        if len(results) == 1:
            return results[0]
        st = [r.state for r in results]
        return _engine.EngineResult(
            _engine.BfsState(
                jnp.concatenate([s.frontier for s in st]),
                jnp.concatenate([s.visited for s in st]),
                jnp.concatenate([s.parent for s in st]),
                jnp.max(jnp.stack([s.layer for s in st]))),
            jnp.concatenate([r.depths for r in results]), None)

    # -- introspection ---------------------------------------------------
    @property
    def traces(self) -> int:
        """Engine traces this plan's executable has paid so far (0 on
        mesh-bound plans — the distributed program jits separately)."""
        return self.executable.traces if self.executable else 0

    def lower(self, roots=None):
        """``jax.jit(...).lower`` of the whole-search program — the
        dry-run/AOT hook.  ``roots`` defaults to a zero batch of the
        plan's ``batch`` width (or 1).  On a mesh-bound plan it lowers
        the one-root ``shard_map`` program every root of ``run`` and
        ``run_batched`` launches."""
        if self.mesh is not None:
            from repro.core import bfs_distributed as dist
            root = 0 if roots is None else np.atleast_1d(
                np.asarray(roots))[0]
            check_roots(root, self.fmt.n_vertices)
            return dist.lower(self._partition, root,
                              self.resolved.max_layers,
                              self.resolved.merge)
        if roots is None:
            roots = jnp.zeros((self.batch or 1,), jnp.int32)
        roots = jnp.atleast_1d(jnp.asarray(roots, jnp.int32))
        return self.executable.run_jit.lower(self.fmt, roots)

    def stats(self, result) -> list[_engine.LayerStats]:
        """Decode a result's on-device stats buffer (Table 1 rows)."""
        return _engine.layer_stats(result)

    def direction_log(self, result) -> list[str]:
        """Per-layer direction strings from a result's stats buffer."""
        return _engine.direction_log(result)

    def __repr__(self) -> str:
        return (f"CompiledTraversal({self.fmt!r}, traces="
                f"{self.traces}, spec={self.resolved})")


def plan(graph, spec: TraversalSpec | None = None, *,
         batch: int | None = None, mesh: Any = None) -> CompiledTraversal:
    """Resolve a spec against a graph and bind the cached executable.

    Args:
      graph: a `Csr`, `EdgeList` or built `formats.GraphFormat` (Csr/
        EdgeList are viewed through `CsrFormat`; pick another layout
        with `formats.autotune.build` first).
      spec: a `TraversalSpec` (default: all-``"auto"``).  Resolved
        exactly once, here.
      batch: optional fixed batch width — `run_batched` pads smaller
        root batches up to it so varying query counts reuse one trace
        (the serving shape).
      mesh: optional jax mesh — ``run`` / ``run_batched`` then execute
        the distributed per-chip program derived from the same
        resolved spec (``merge``/``max_layers``) over the CSR, which
        is partitioned over the mesh here, once.  Without it, the mesh
        set by ``jax.set_mesh`` is used when it spans more than one
        device.
    """
    # admission-time structural validation (ISSUE 8): raw Csr inputs
    # are checked BEFORE as_format wraps them (CsrFormat's int() ctor
    # would turn NaN geometry into an untyped ValueError), built
    # formats through their own validate_structure hook
    from repro.core.csr import Csr as _Csr, check_structure
    if isinstance(graph, _Csr):
        check_structure(graph)
    fmt = as_format(graph)
    fmt.validate_structure()
    spec = spec if spec is not None else TraversalSpec()
    if mesh is None and jax.sharding.get_abstract_mesh().size > 1:
        mesh = jax.sharding.get_mesh()      # the one jax.set_mesh set
    if mesh is not None:
        # same contract as run_bfs_distributed(spec=): flag
        # explicitly-set fields the fixed per-chip program cannot
        # honor, and skip the autotune policy measurement it would
        # never read
        from repro.api.spec import warn_mesh_ignored_fields
        warn_mesh_ignored_fields(spec, "mesh-bound plan")
        if spec.policy == "auto":
            spec = spec.replace(policy="topdown")
    resolved = spec.resolve(fmt)
    # mesh-bound plans never run the single-chip executable (their
    # run() is the shard_map program) — don't pollute the cache
    ex = None if mesh is not None else _executable(fmt, resolved)
    return CompiledTraversal(fmt, resolved, ex, batch=batch, mesh=mesh)


def _mesh_partition(fmt, mesh):
    """The mesh path's CSR partition (`bfs_distributed.partition_on_mesh`)."""
    from repro.core import bfs_distributed as dist
    to_csr = getattr(fmt, "to_csr", None)
    if to_csr is None:
        raise TypeError(
            f"mesh-bound plans need a CSR-recoverable format; "
            f"{type(fmt).__name__} has no to_csr()")
    return dist.partition_on_mesh(to_csr(), mesh)
