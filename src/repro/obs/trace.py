"""Span tracer + instrumented host-stepped traversal.

The engine's fused ``lax.while_loop`` (PR 1) deliberately has no host
synchronization inside the layer loop — which is exactly why nothing
can time its layers.  This module adds the *time* axis without
touching that fast path:

* `span` — the one span primitive: a ``jax.profiler.TraceAnnotation``.
  With a profiler session open it lands on the host plane of the same
  ``.xplane.pb`` as the device ops, on the same clock, its keyword
  args as event stats; with none it costs about a microsecond, so
  the serve tick (`serve.graph_engine`) keeps its spans on always.
* `SpanTracer` — a context-manager span recorder (nesting:
  traversal → layer → step) that exports Chrome trace-event JSON;
  open ``chrome://tracing`` or https://ui.perfetto.dev and load the
  file.  Each of its spans also enters `span`, so it shows on a
  profiler timeline too.  Its own times are wall-clock
  (``time.perf_counter``); callers pass
  device arrays to `SpanTracer.device_sync` so a span's close waits
  for the device work it timed (otherwise JAX's async dispatch would
  attribute everything to the first sync).
* `trace_run` — the instrumented traversal: a host Python layer loop
  over the plan cache's compiled single-layer tick
  (`CompiledTraversal.layer_step`, the same executable the serve tier
  ticks), so per-layer wall times attach to the familiar `LayerStats`
  rows.  The fused whole-search program is never modified — tracing
  is a *mode you opt into*, not overhead the fast path pays.
* `xla_profiler` — gated pass-through to ``jax.profiler.start_trace``
  for full XLA/TensorBoard profiles; combined with the
  ``jax.named_scope`` annotations on every Pallas wrapper in
  `kernels/ops.py`, device time shows up attributed to named BFS
  phases (``bfs.gather_expand``, ``bfs.frontier_compact``, ...).

The host-stepped loop pays one device sync per layer — that is the
price of per-layer timing, and the reason `trace_run` is a separate
entry point instead of a flag that silently de-fuses ``run``.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmap as bm
from repro.core import engine as _engine

#: span names — the obs-smoke gate greps for these
TRAVERSAL_SPAN = "bfs.traversal"
LAYER_SPAN = "bfs.layer"
STEP_SPAN = "bfs.layer_step"
#: the whole-traversal persistent pipeline (ISSUE 9) is ONE Pallas
#: launch — there is no per-layer host boundary to time, so trace_run
#: records ONE span of this name and recovers per-layer counters from
#: the kernel's on-device stats buffer instead of host recomputation
PERSISTENT_SPAN = "bfs.traversal.persistent"
#: the semiring portfolio (ISSUE 10: sssp/cc/ksource_bfs) runs the
#: whole traversal through the portfolio driver's fused while_loop —
#: like the persistent pipeline there is no host layer boundary, so
#: trace_run records ONE span of this name and recovers per-layer
#: counters from the driver's on-device stats buffer
SEMIRING_SPAN = "bfs.traversal.semiring"


def span(name: str, **args: Any) -> jax.profiler.TraceAnnotation:
    """A span on the profiler's clock: ``with span("serve.tick",
    tick=3) as sp: ...``.  ``args`` (numbers or strings) become the
    event's stats; ``sp.set_metadata(**more)`` adds stats known only
    inside the block.  Recorded only while a ``jax.profiler`` trace is
    open."""
    return jax.profiler.TraceAnnotation(name, **args)


@dataclass
class Span:
    """One closed span: microsecond offset + duration relative to the
    tracer's origin, plus free-form ``args`` shown in the trace UI."""
    name: str
    ts_us: float = 0.0
    dur_us: float = 0.0
    tid: int = 1
    args: dict = field(default_factory=dict)


class SpanTracer:
    """Records nested wall-clock spans; exports Chrome trace events.

    Usage::

        tr = SpanTracer()
        with tr.span("bfs.traversal", n_roots=4):
            with tr.span("bfs.layer", layer=0):
                ...work...
        tr.export("obs_trace.json")      # load in Perfetto

    ``sync=True`` (default) makes `device_sync` call
    ``jax.block_until_ready`` so spans measure finished device work,
    not dispatch latency; ``sync=False`` turns every `device_sync`
    into a no-op (time the async dispatch itself).
    """

    def __init__(self, sync: bool = True):
        self.sync = sync
        self.spans: list[Span] = []
        self._origin = time.perf_counter()
        self._stack: list[Span] = []

    def _now_us(self) -> float:
        return (time.perf_counter() - self._origin) * 1e6

    @contextlib.contextmanager
    def span(self, name: str, **args: Any) -> Iterator[Span]:
        """Open a span; closes (records duration) on exit.  Extra
        kwargs become the trace event's ``args`` and may be amended on
        the yielded `Span` before exit."""
        s = Span(name, args=dict(args))
        with span(name, **args):
            self._stack.append(s)
            s.ts_us = self._now_us()
            try:
                yield s
            finally:
                s.dur_us = self._now_us() - s.ts_us
                self._stack.pop()
                self.spans.append(s)

    def device_sync(self, *arrays) -> None:
        """Wait for device work (``jax.block_until_ready``) so the
        enclosing span's close time is honest.  No-op when the tracer
        was built with ``sync=False``."""
        if self.sync:
            jax.block_until_ready(arrays)

    # -- export ----------------------------------------------------------
    def to_chrome(self) -> dict:
        """Chrome trace-event JSON object (the ``traceEvents`` array
        of complete "X" events).  Nesting is implied by time
        containment on the shared tid — exactly how Perfetto draws
        flame stacks."""
        pid = os.getpid()
        events: list[dict] = [{
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": "repro.bfs"},
        }]
        for s in sorted(self.spans, key=lambda s: s.ts_us):
            events.append({
                "name": s.name, "cat": "bfs", "ph": "X",
                "ts": round(s.ts_us, 3), "dur": round(s.dur_us, 3),
                "pid": pid, "tid": s.tid, "args": s.args,
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        """Write the Chrome trace JSON to ``path``; returns the path."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f, indent=1)
        return path

    def __len__(self) -> int:
        return len(self.spans)


@contextlib.contextmanager
def xla_profiler(logdir: str | None):
    """``jax.profiler.start_trace``/``stop_trace`` around a block when
    the installed jax exposes it AND ``logdir`` is set; a silent no-op
    otherwise (CPU wheels without profiler support, logdir=None).
    Combined with the `kernels.ops` ``jax.named_scope`` annotations,
    the resulting TensorBoard/Perfetto profile attributes device time
    to named BFS phases."""
    if logdir is None or not hasattr(jax.profiler, "start_trace"):
        yield None
        return
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


class TraceRun(NamedTuple):
    """What `trace_run` returns: the usual engine outputs plus timing.

    ``stats[i]`` and ``layer_seconds[i]`` describe the same layer —
    the per-layer timing "attached to the LayerStats row".  ``state``
    and ``depths`` match `EngineResult` semantics (unbatched when a
    scalar root was passed)."""
    state: _engine.BfsState
    depths: jax.Array                     # (B,) or scalar int32
    stats: list[_engine.LayerStats]
    layer_seconds: list[float]
    tracer: SpanTracer


def trace_run(graph, roots, *, spec=None, tracer: SpanTracer | None = None,
              sync: bool = True, profile_logdir: str | None = None
              ) -> TraceRun:
    """Instrumented traversal: per-layer wall-clock spans + counters.

    Runs a host Python layer loop over the *plan cache's* compiled
    single-layer tick — the same `layer_jit` executable
    `CompiledTraversal.layer_step` and the serve tier use — so this
    mode adds zero new compiles beyond the layer tick and never
    perturbs the fused ``lax.while_loop`` program.  Each layer pays
    one ``block_until_ready`` sync (that is what buys honest
    timings); per-layer Table 1 counters (frontier vertices, edges
    examined, discovered) are recomputed host-side from word popcounts
    and the word-aligned degree matrix, identical to the fused
    engine's on-device accounting.

    Args:
      graph: a `Csr`/`EdgeList`/`GraphFormat` (planned here) or an
        existing `repro.bfs.CompiledTraversal` (reused — zero extra
        traces when it has already run).
      roots: int (unbatched result) or sequence (leading root axis).
      spec: optional `TraversalSpec` when ``graph`` is not already a
        plan.  The layer tick runs the spec's fixed SIMD/scalar step
        (``algorithm``); direction *policies* decide inside the fused
        program and do not apply to the host-stepped mode.
      tracer: record into an existing `SpanTracer` (default: fresh
        one with ``sync=``).
      sync: block on device work at span close (see `SpanTracer`).
      profile_logdir: also wrap the loop in `xla_profiler`.

    Returns a `TraceRun`; ``len(stats) == len(layer_seconds)`` == the
    number of layer spans recorded (the obs-smoke acceptance gate).
    """
    from repro.api.plan import CompiledTraversal, plan as _plan
    ct = (graph if isinstance(graph, CompiledTraversal)
          else _plan(graph, spec))
    if ct.mesh is not None:
        raise NotImplementedError(
            "trace_run hosts the single-chip layer tick; mesh-bound "
            "plans have no per-layer step to instrument")
    tracer = tracer if tracer is not None else SpanTracer(sync=sync)
    fmt, rspec = ct.fmt, ct.resolved
    n_vertices, v_pad = fmt.n_vertices, fmt.n_vertices_padded

    single = jnp.ndim(roots) == 0
    roots_b = jnp.atleast_1d(jnp.asarray(roots, jnp.int32))
    n_roots = int(roots_b.shape[0])

    if rspec.is_semiring:
        # ONE run, ONE span: the portfolio driver owns the
        # value/frontier carry inside a fused while_loop, so (like
        # the persistent pipeline) there is no per-layer host
        # boundary; Table 1-equivalent counters come back from the
        # driver's stats buffer and the per-layer seconds are the
        # span amortized over the recovered layers.
        with xla_profiler(profile_logdir), \
             tracer.span(SEMIRING_SPAN, n_roots=n_roots,
                         format=type(fmt).__name__,
                         pipeline=rspec.pipeline,
                         algorithm=rspec.algorithm,
                         n_vertices=n_vertices) as top:
            res = ct.run_batched(roots_b)
            tracer.device_sync(res.state.frontier, res.state.parent,
                               res.values, res.stats)
            stats = _engine.layer_stats(res)
            top.args["n_layers"] = len(stats)
            top.args["launches"] = sum(s.launches for s in stats)
            top.args["relaxations"] = sum(s.edges_examined
                                          for s in stats)
        per_layer_s = (top.dur_us / 1e6) / max(len(stats), 1)
        layer_seconds = [per_layer_s] * len(stats)
        state, depths_j = res.state, res.depths
        if single:
            state = _engine.BfsState(state.frontier[0],
                                     state.visited[0],
                                     state.parent[0], state.layer)
            depths_j = depths_j[0]
        return TraceRun(state, depths_j, stats, layer_seconds, tracer)

    if rspec.pipeline == "persistent":
        # ONE launch, ONE span: the layer loop runs inside the kernel
        # (ISSUE 9), so there is no per-layer host boundary to time.
        # Per-layer Table 1 counters come back from the kernel's
        # on-device stats buffer (`engine.layer_stats`); the per-layer
        # seconds are the single span's duration amortized over the
        # recovered layers — the honest figure when layers cannot be
        # individually observed (len(stats) == len(layer_seconds)
        # still holds for every consumer).
        with xla_profiler(profile_logdir), \
             tracer.span(PERSISTENT_SPAN, n_roots=n_roots,
                         format=type(fmt).__name__,
                         pipeline=rspec.pipeline,
                         algorithm=rspec.algorithm,
                         n_vertices=n_vertices) as top:
            res = ct.run_batched(roots_b)
            tracer.device_sync(res.state.frontier, res.state.visited,
                               res.state.parent, res.stats)
            stats = _engine.layer_stats(res)
            top.args["n_layers"] = len(stats)
            top.args["launches"] = sum(s.launches for s in stats)
            top.args["layers"] = [
                {"frontier_vertices": s.frontier_vertices,
                 "edges_examined": s.edges_examined,
                 "discovered": s.discovered} for s in stats]
        per_layer_s = (top.dur_us / 1e6) / max(len(stats), 1)
        layer_seconds = [per_layer_s] * len(stats)
        state, depths_j = res.state, res.depths
        if single:
            state = _engine.BfsState(state.frontier[0], state.visited[0],
                                     state.parent[0], state.layer)
            depths_j = depths_j[0]
        return TraceRun(state, depths_j, stats, layer_seconds, tracer)

    deg_mat = bm.degree_matrix(fmt.degrees(), v_pad)

    stats: list[_engine.LayerStats] = []
    layer_seconds: list[float] = []
    depths = np.zeros((n_roots,), np.int32)

    with xla_profiler(profile_logdir), \
         tracer.span(TRAVERSAL_SPAN, n_roots=n_roots,
                     format=type(fmt).__name__, pipeline=rspec.pipeline,
                     algorithm=rspec.algorithm, n_vertices=n_vertices
                     ) as top:
        with tracer.span("bfs.init"):
            frontier, visited, parent = _engine._init_batched(
                roots_b, n_vertices, v_pad)
            tracer.device_sync(frontier, visited, parent)
        layer = 0
        while layer < rspec.max_layers:
            f_count_b = np.asarray(_engine.row_popcounts(frontier))
            f_count = int(f_count_b.sum())
            if f_count == 0:
                break
            f_edges = int(np.asarray(jax.vmap(
                lambda w: bm.masked_degree_sum(w, deg_mat))(frontier)
            ).sum())
            with tracer.span(LAYER_SPAN, layer=layer,
                             frontier_vertices=f_count,
                             edges_examined=f_edges) as lsp:
                with tracer.span(STEP_SPAN, layer=layer):
                    frontier, visited, parent = ct.layer_step(
                        frontier, visited, parent)
                    tracer.device_sync(frontier, visited, parent)
                discovered = int(_engine.row_popcounts(frontier).sum())
                lsp.args["discovered"] = discovered
            stats.append(_engine.LayerStats(
                layer=layer, frontier_vertices=f_count,
                edges_examined=f_edges, discovered=discovered))
            layer_seconds.append(lsp.dur_us / 1e6)
            depths += (f_count_b > 0).astype(np.int32)
            layer += 1
        top.args["n_layers"] = layer

    state = _engine.BfsState(frontier, visited, parent, jnp.int32(layer))
    depths_j = jnp.asarray(depths)
    if single:
        state = _engine.BfsState(state.frontier[0], state.visited[0],
                                 state.parent[0], state.layer)
        depths_j = depths_j[0]
    return TraceRun(state, depths_j, stats, layer_seconds, tracer)
