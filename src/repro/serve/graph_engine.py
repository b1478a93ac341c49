"""Continuous-batching BFS query service over one resident graph.

The graph analogue of `serve.engine.ServeEngine`: a request pool, a
fixed query batch with slot reuse (a finished query's slot is refilled
from the queue on the next tick — "continuous batching"), and a batch
shape that never changes so the jitted tick compiles exactly once.

One tick == one BFS layer for EVERY active slot, via the plan layer's
single-layer executable (`repro.bfs.plan(...).layer_step`, leading
root axis).  Since ISSUE 3 the ``algorithm="simd"`` tick routes
through the fused gather pipeline: each slot's frontier plans its own
active-tile work-list, so slots whose frontier has emptied flow
through as true no-ops — their work-list is empty (n_active == 0),
costing zero DMA tiles instead of a full sentinel edge stream — until
the host harvests the parent array and refills the slot.  The
per-tick host sync (a (B,) frontier-count readback) is the serving
tick boundary, exactly like ServeEngine's per-token logits readback;
whole-query throughput without any tick sync is what a root-batched
`CompiledTraversal.run_batched` provides.

**Preprocess-on-load** (the formats scenario axis): the engine picks
a graph layout per resident graph at construction —
``graph_format="auto"`` runs the `formats.autotune` decision on the
graph's degree statistics; any registered name forces that layout.
Since ISSUE 5 the remaining configuration is ONE `TraversalSpec`
(``spec=``): the engine stores a `CompiledTraversal` instead of six
loose attributes, and the tick hits that plan's cached executable.

**Robustness** (ISSUE 8): the queue is *bounded* — `submit` returns a
typed `serve.robust.AdmissionDecision` or raises
`repro.errors.QueueFullError` / `AdmissionRejected` (backpressure
instead of unbounded latency); queries carry optional wall-clock
deadlines (`repro.errors.DeadlineExceeded` attached to the truncated
result) and per-query layer budgets; a failed device tick retries
with capped exponential backoff and, on exhaustion, re-queues every
in-flight query before raising `TickRetriesExhausted` (zero lost
queries); every harvested result passes a sanity check (root
self-parented, ids in range) and a corrupted slot is re-run instead
of delivered; and the ``serve.circuit_state`` gauge exports the
healthy/degraded/shedding breaker position.  Chaos coverage drives a
`serve.robust.ServeFaultInjector` through all of it
(``make chaos-smoke``).

**Spans** (`obs.trace.span`, on the profiler's clock; recorded
whenever a ``jax.profiler`` trace is open, about a microsecond each
otherwise).  Every `GraphEngine.step` is one ``serve.tick`` (args
``tick``, ``active``) holding, in order:

* ``serve.fill`` — queue expiry and slot refill (the root transfers
  and ``_reset_slot`` dispatches); arg ``refilled``;
* ``serve.dispatch`` — the enqueue of ``layer_step``, retries
  included; arg ``attempts``;
* ``serve.readback`` — the frontier popcount readback, which waits
  for the tick's device work;
* ``serve.harvest`` — one per delivered or re-queued slot (sanity
  check and result copy); args ``uid``, ``layers``.

A tick with no active slot holds only ``serve.fill``.  Each query's
``meta`` carries its lifecycle on the ``time.perf_counter`` clock:
``submit_t`` (arrival; set by `submit` unless the caller set it),
``slot_t`` (the last time `step` placed it in a slot) and
``harvest_t`` (delivery), read by the ``serve.queue_wait_s`` and
``serve.in_slot_s`` histograms.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmap as bm
from repro.core import engine
from repro.errors import (AdmissionRejected, DeadlineExceeded,
                          QueueFullError, TickRetriesExhausted)
from repro.obs import metrics as obs_metrics
from repro.obs.trace import span
from repro.serve import robust


@functools.partial(jax.jit, static_argnames=("slot", "n_vertices"))
def _reset_slot(frontier, visited, parent, base_visited, root, *,
                slot: int, n_vertices: int):
    """Re-arm one batch slot for a fresh root (masked row updates).

    Module-level so the jit cache survives across GraphEngine
    instances (compiles once per (batch shape, slot))."""
    f_row, vis_row, p_row = engine.init_root_state(root, base_visited,
                                                   n_vertices)
    return (frontier.at[slot].set(f_row),
            visited.at[slot].set(vis_row),
            parent.at[slot].set(p_row))




@dataclass
class BfsQuery:
    uid: int
    root: int
    parent: np.ndarray | None = None   # Graph500 convention (-1 unreached)
    n_layers: int = 0
    done: bool = False
    truncated: bool = False            # hit a budget (layers/deadline):
    #                                    the parent array is PARTIAL
    #                                    (-1 may mean "not reached
    #                                    yet") or None (never ran)
    priority: int = 0                  # admission order; shedding floor
    deadline_s: float | None = None    # wall-clock budget from submit
    max_layers: int | None = None      # per-query layer budget override
    #                                    (None = the engine spec's)
    error: Exception | None = None     # typed degradation record —
    #                                    DeadlineExceeded on budget
    #                                    expiry; None on clean finishes
    retries: int = 0                   # times this query was re-run
    #                                    (tick failure / poisoned slot)
    meta: dict = field(default_factory=dict)
    #  lifecycle stamps (``time.perf_counter`` seconds): a caller may
    #  set ``meta["submit_t"]`` to the query's arrival time before
    #  `GraphEngine.submit`, which otherwise stamps it; the engine
    #  stamps ``slot_t`` at each slot fill and ``harvest_t`` on
    #  delivery


class GraphEngine:
    """Serve many concurrent BFS queries against one device-resident
    graph.

    Args:
      graph: the resident graph — a `Csr` or an already-built
        `formats.GraphFormat` (stays on device for the engine's
        lifetime).
      batch_slots: fixed query-batch width (compiled once).
      graph_format: layout for the tick — "auto" (autotune from graph
        statistics, the default), any registered format name, or None
        to wrap a Csr as-is.  A passed-in built format is kept under
        "auto"/None (the caller already chose); forcing a *different*
        name re-lays it out when the format can recover its CSR
        (`to_csr`) and raises a TypeError otherwise.
      spec: a `repro.bfs.TraversalSpec` — the ONE configuration object
        for the tick (algorithm, pipeline, packed, prefetch_depth,
        tile) and the per-query layer budget (``max_layers``; "auto"
        = 64).  Resolved once at construction; the engine stores the
        resulting `CompiledTraversal` (``self.compiled``), whose
        cached executable every tick hits.
      algorithm/max_layers/pipeline/packed/prefetch_depth: deprecated
        loose-knob form of the same fields (kept for compatibility;
        emits DeprecationWarning).
      registry: a `repro.obs.MetricsRegistry` to record serving
        metrics into (default: the process registry,
        `repro.obs.get_registry()`).  Recorded under ``serve.*``:
        per-query submit→harvest latency (``serve.query_latency_s``
        histogram — p50/p99 in its snapshot; from arrival where the
        caller set ``meta["submit_t"]``) and its two parts,
        submit→slot (``serve.queue_wait_s``) and slot→harvest
        (``serve.in_slot_s``), tick duration
        (``serve.tick_s``), queue depth / slot occupancy /
        circuit-state gauges, and tick/query/skip/reject/retry
        counters.
      queue_capacity: bounded submit-queue size (default
        ``16 * batch_slots``).  At capacity `submit` raises
        `QueueFullError` — explicit backpressure instead of unbounded
        queueing.  Ignored when ``admission`` is passed.
      admission: a full `serve.robust.AdmissionPolicy` (capacity,
        degraded depth, optional priority-shedding floor); overrides
        ``queue_capacity``.
      injector: a `serve.robust.ServeFaultInjector` — chaos-test hook
        firing failures/stalls/poisoned rows at configured ticks.
      max_tick_retries: device-tick retry budget (capped exponential
        backoff between attempts); on exhaustion every in-flight
        query is re-queued and `TickRetriesExhausted` raises.
      retry_backoff_s: backoff base for `serve.robust.backoff_s`.
    """

    def __init__(self, graph, batch_slots: int = 8,
                 algorithm=engine._UNSET, max_layers=engine._UNSET,
                 graph_format: str | None = "auto",
                 pipeline=engine._UNSET, packed=engine._UNSET,
                 prefetch_depth=engine._UNSET, spec=None,
                 registry: obs_metrics.MetricsRegistry | None = None,
                 queue_capacity: int | None = None,
                 admission: robust.AdmissionPolicy | None = None,
                 injector: robust.ServeFaultInjector | None = None,
                 max_tick_retries: int = 3,
                 retry_backoff_s: float = 0.01):
        from repro.api.plan import plan as _plan
        from repro.core.csr import Csr as _Csr, check_structure
        from repro.formats import GraphFormat, autotune
        # admission-time validation (ISSUE 8): a raw Csr is checked
        # BEFORE autotune re-lays it out — a malformed graph must be
        # a typed construction error, not a wrong resident layout
        if isinstance(graph, _Csr):
            check_structure(graph)
        if isinstance(graph, GraphFormat):
            self.csr = None
            self.fmt = (graph if graph_format in (None, "auto",
                                                  graph.name)
                        else autotune.build(graph, graph_format))
        else:
            self.csr = graph
            self.fmt = autotune.build(graph, graph_format or "csr")
        # the tick never evaluates a direction policy; "auto" and the
        # neutral TopDown (object or registered name — what
        # make_spec/legacy knobs pin) pass silently, anything else
        # was a real configuration intent
        if spec is not None \
                and spec.policy not in ("auto", "topdown") \
                and spec.policy != engine.TopDown():
            import warnings
            warnings.warn(
                "GraphEngine: the serve tick is policy-free (one "
                "layer per tick; scalar vs SIMD comes from "
                "spec.algorithm) — spec.policy is ignored",
                UserWarning, stacklevel=2)
        spec = engine._spec_from_knobs(
            "GraphEngine", spec,
            dict(algorithm=algorithm, max_layers=max_layers,
                 pipeline=pipeline, packed=packed,
                 prefetch_depth=prefetch_depth))
        if spec.policy == "auto":
            # pin a concrete policy the tick never reads: skips the
            # autotune measurement and keeps .resolved honest about
            # the direction machinery not running here
            spec = spec.replace(policy="topdown")
        if spec.is_semiring:
            # the tick contract is one BFS layer per slot; the
            # portfolio driver owns its own value/frontier carry and
            # has no single-layer tick — route those queries through
            # the dedicated methods instead of the resident spec
            raise ValueError(
                f"GraphEngine's tick spec cannot use the semiring "
                f"algorithm {spec.algorithm!r}: the slot machinery "
                f"advances one BFS layer per tick — use "
                f"shortest_paths()/components()/ksource_depths() "
                f"(run-direct portfolio queries), and keep spec."
                f"algorithm a scalar value or 'auto'")
        self.compiled = _plan(self.fmt, spec)
        b = batch_slots
        self.n_vertices = self.fmt.n_vertices
        v_pad = self.fmt.n_vertices_padded
        w = v_pad // bm.BITS_PER_WORD
        self.frontier = jnp.zeros((b, w), jnp.uint32)
        self.visited = jnp.zeros((b, w), jnp.uint32)
        self.parent = jnp.full((b, v_pad), self.n_vertices, jnp.int32)
        self._base_visited = self.fmt.init_visited()
        self.slots: list[BfsQuery | None] = [None] * b
        # bounded priority queue (ISSUE 8): higher priority first,
        # FIFO within a level; at capacity `submit` rejects with a
        # typed error instead of queueing unboundedly
        if admission is None:
            cap = (int(queue_capacity) if queue_capacity is not None
                   else 16 * b)
            admission = robust.AdmissionPolicy(
                queue_capacity=cap, degraded_depth=max(1, cap // 2))
        self.admission = admission
        self.queue = robust.AdmissionQueue(admission.queue_capacity)
        self.injector = injector
        self.max_tick_retries = int(max_tick_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self._tick_no = 0
        self.finished: list[BfsQuery] = []
        # serving metrics (ISSUE 7): the operational distributions the
        # ROADMAP serve-SLO work will budget against
        self.metrics = (registry if registry is not None
                        else obs_metrics.get_registry())
        self._m_latency = self.metrics.histogram(
            "serve.query_latency_s",
            "submit->harvest wall seconds per query")
        self._m_queue_wait = self.metrics.histogram(
            "serve.queue_wait_s",
            "submit->slot wall seconds per query (its last slot fill)")
        self._m_in_slot = self.metrics.histogram(
            "serve.in_slot_s",
            "slot->harvest wall seconds per query (its last slot fill)")
        self._m_tick = self.metrics.histogram(
            "serve.tick_s", "wall seconds per engine tick")
        self._m_queue = self.metrics.gauge(
            "serve.queue_depth", "queries waiting for a slot")
        self._m_occupancy = self.metrics.gauge(
            "serve.slot_occupancy", "active slots / batch_slots")
        self._m_ticks = self.metrics.counter(
            "serve.ticks", "engine ticks that dispatched a layer_step")
        self._m_skipped = self.metrics.counter(
            "serve.ticks_skipped",
            "ticks short-circuited with no active slot (no device "
            "dispatch)")
        self._m_submitted = self.metrics.counter(
            "serve.queries_submitted")
        self._m_finished = self.metrics.counter("serve.queries_finished")
        self._m_truncated = self.metrics.counter(
            "serve.queries_truncated",
            "queries harvested PARTIAL at a layers/deadline budget")
        # robustness counters (ISSUE 8)
        self._m_rejected = self.metrics.counter(
            "serve.rejected",
            "submits refused by admission control (queue full / "
            "priority shed)")
        self._m_retries = self.metrics.counter(
            "serve.retries", "failed device-tick attempts retried")
        self._m_requeued = self.metrics.counter(
            "serve.requeued",
            "in-flight queries re-queued after tick failure or a "
            "corrupted slot")
        self._m_poisoned = self.metrics.counter(
            "serve.poisoned",
            "corrupted slot results caught by the harvest sanity "
            "check (re-run, never delivered)")
        self._m_deadline = self.metrics.counter(
            "serve.deadline_exceeded",
            "queries harvested with a DeadlineExceeded error")
        self._m_circuit = self.metrics.gauge(
            "serve.circuit_state",
            "admission circuit: 0=healthy 1=degraded 2=shedding")
        # algorithm-portfolio counters (ISSUE 10)
        self._m_portfolio = self.metrics.counter(
            "serve.portfolio_queries",
            "semiring portfolio queries (shortest_paths/components/"
            "ksource_depths) answered run-direct")
        self._semiring_plans: dict[str, object] = {}

    # -- resolved-spec views (legacy attribute compatibility) -----------
    @property
    def resolved(self):
        """The fully-concrete `TraversalSpec` the tick runs."""
        return self.compiled.resolved

    @property
    def algorithm(self) -> str:
        return self.compiled.resolved.algorithm

    @property
    def pipeline(self) -> str:
        return self.compiled.resolved.pipeline

    @property
    def packed(self) -> bool:
        return self.compiled.resolved.packed

    @property
    def prefetch_depth(self) -> int:
        return self.compiled.resolved.prefetch_depth

    @property
    def max_layers(self) -> int:
        return self.compiled.resolved.max_layers

    # -- admission (ISSUE 8) --------------------------------------------
    def circuit_state(self) -> str:
        """Current breaker position (`serve.robust.CIRCUIT_*`)."""
        depth = len(self.queue)
        if self.queue.full:
            return robust.CIRCUIT_SHEDDING
        if (self._active_slots() == len(self.slots)
                and depth >= self.admission.degraded_depth):
            return robust.CIRCUIT_DEGRADED
        return robust.CIRCUIT_HEALTHY

    def _set_circuit_gauge(self, state: str | None = None) -> str:
        state = state if state is not None else self.circuit_state()
        self._m_circuit.set(robust.CIRCUIT_CODES[state])
        return state

    def try_submit(self, query: BfsQuery) -> robust.AdmissionDecision:
        """Admission decision without raising: validates the root
        (typed `GraphValidationError` — malformed input is a client
        bug, not backpressure), then admits or rejects per the
        circuit."""
        from repro.api.plan import check_roots
        check_roots(query.root, self.n_vertices)
        state = self._set_circuit_gauge()
        depth = len(self.queue)
        if state == robust.CIRCUIT_SHEDDING:
            self._m_rejected.inc()
            return robust.AdmissionDecision(
                admitted=False, circuit=state, queue_depth=depth,
                reason=(f"queue at capacity "
                        f"({depth}/{self.queue.capacity})"))
        floor = self.admission.shed_min_priority
        if (state == robust.CIRCUIT_DEGRADED and floor is not None
                and query.priority < floor):
            self._m_rejected.inc()
            return robust.AdmissionDecision(
                admitted=False, circuit=state, queue_depth=depth,
                reason=(f"load shedding: priority {query.priority} < "
                        f"floor {floor} while degraded"))
        query.meta.setdefault("submit_t", time.perf_counter())
        self.queue.push(query, query.priority)
        self._m_submitted.inc()
        self._m_queue.set(len(self.queue))
        self._set_circuit_gauge()
        return robust.AdmissionDecision(
            admitted=True, circuit=state, queue_depth=len(self.queue))

    def submit(self, query: BfsQuery) -> robust.AdmissionDecision:
        """Admit ``query`` or raise the typed rejection
        (`QueueFullError` at capacity, `AdmissionRejected` when
        priority-shed); returns the `AdmissionDecision` on admit."""
        decision = self.try_submit(query)
        if not decision.admitted:
            cls = (QueueFullError
                   if decision.circuit == robust.CIRCUIT_SHEDDING
                   else AdmissionRejected)
            raise cls(f"query uid={query.uid} rejected: "
                      f"{decision.reason}", decision=decision)
        return decision

    def _expire_queued(self) -> None:
        """Harvest queued queries whose deadline passed before they
        ever got a slot (parent=None — they never ran)."""
        now = time.perf_counter()

        def expired(q):
            return (q.deadline_s is not None
                    and now - q.meta.get("submit_t", now) > q.deadline_s)

        for q in self.queue.remove_if(expired):
            elapsed = now - q.meta.get("submit_t", now)
            q.error = DeadlineExceeded(
                f"query uid={q.uid} expired after {elapsed:.3f}s in "
                f"the queue (deadline_s={q.deadline_s}) without ever "
                f"getting a slot", uid=q.uid, elapsed_s=elapsed,
                budget_s=q.deadline_s, where="queued")
            q.parent = None
            q.truncated = True
            q.done = True
            self.finished.append(q)
            self._m_finished.inc()
            self._m_truncated.inc()
            self._m_deadline.inc()
        self._m_queue.set(len(self.queue))

    def _fill_slots(self) -> int:
        """Place queued queries in free slots; returns how many."""
        refilled = 0
        for i, q in enumerate(self.slots):
            if (q is None or q.done) and self.queue:
                nxt = self.queue.pop()
                nxt.meta["slot_t"] = time.perf_counter()
                self.slots[i] = nxt
                self.frontier, self.visited, self.parent = _reset_slot(
                    self.frontier, self.visited, self.parent,
                    self._base_visited, jnp.asarray(nxt.root, jnp.int32),
                    slot=i, n_vertices=self.n_vertices)
                refilled += 1
        self._m_queue.set(len(self.queue))
        return refilled

    def _active_slots(self) -> int:
        return sum(q is not None and not q.done for q in self.slots)

    # -- result integrity / recovery (ISSUE 8) --------------------------
    def _result_ok(self, i: int, q: BfsQuery) -> bool:
        """Harvest-time sanity check: the root must be self-parented
        and every entry a legal id (device convention: unreached ==
        sentinel ``n_vertices``).  A violation means the slot's state
        was corrupted (e.g. an injected poisoned result) — the query
        is re-run, never delivered."""
        p = np.asarray(self.parent[i, :self.n_vertices])
        if int(p[q.root]) != q.root:
            return False
        return bool(((p >= 0) & (p <= self.n_vertices)).all())

    def _requeue(self, i: int, q: BfsQuery) -> None:
        """Re-run ``q`` from its root: reset its progress and force it
        back onto the queue (past capacity if need be — the engine's
        own recovery must never lose a query to its own
        backpressure)."""
        q.n_layers = 0
        q.done = False
        q.truncated = False
        q.parent = None
        q.retries += 1
        self.slots[i] = None
        self.queue.push(q, q.priority, force=True)
        self._m_requeued.inc()
        self._m_queue.set(len(self.queue))

    def _requeue_in_flight(self) -> None:
        for i, q in enumerate(self.slots):
            if q is not None and not q.done:
                self._requeue(i, q)

    def _dispatch_with_retry(self, tick_no: int) -> int:
        """Run the device tick, retrying with capped exponential
        backoff; returns the attempts it took.
        `CompiledTraversal.layer_step` is functional (new
        arrays out; assignment only on success), so a failed attempt
        cannot corrupt slot state.  On exhaustion every in-flight
        query is re-queued (restart from root) and
        `TickRetriesExhausted` raises — a loud infrastructure error
        with zero lost queries."""
        last: Exception | None = None
        for attempt in range(self.max_tick_retries + 1):
            try:
                if self.injector is not None:
                    stall = self.injector.stall_s(tick_no)
                    if stall > 0:
                        time.sleep(stall)
                    self.injector.check_tick(tick_no)
                self.frontier, self.visited, self.parent = \
                    self.compiled.layer_step(
                        self.frontier, self.visited, self.parent)
                return attempt + 1
            except Exception as exc:    # noqa: BLE001 — retry any
                last = exc              # device-step failure flavour
                self._m_retries.inc()
                if attempt < self.max_tick_retries:
                    time.sleep(robust.backoff_s(
                        attempt, self.retry_backoff_s))
        self._requeue_in_flight()
        raise TickRetriesExhausted(
            f"serve tick {tick_no} failed {self.max_tick_retries + 1} "
            f"times; {self._m_requeued.value:g} in-flight queries "
            f"re-queued (none lost) — last error: {last!r}") from last

    def _harvest(self, i: int, q: BfsQuery, truncated: bool = False,
                 error: Exception | None = None,
                 check: bool = True) -> bool:
        """Deliver slot ``i``'s result; returns False when the sanity
        check caught a corrupted slot (the query was re-queued
        instead)."""
        if check and not self._result_ok(i, q):
            self._m_poisoned.inc()
            self._requeue(i, q)
            return False
        p = np.asarray(self.parent[i, :self.n_vertices])
        q.parent = np.where(p >= self.n_vertices, -1, p)
        q.truncated = truncated
        q.error = error
        q.done = True
        self.finished.append(q)
        self._m_finished.inc()
        if truncated:
            self._m_truncated.inc()
        if isinstance(error, DeadlineExceeded):
            self._m_deadline.inc()
        now = q.meta["harvest_t"] = time.perf_counter()
        t_slot = q.meta["slot_t"]
        self._m_in_slot.observe(now - t_slot)
        t0 = q.meta.get("submit_t")
        if t0 is not None:
            q.meta["latency_s"] = now - t0
            self._m_latency.observe(q.meta["latency_s"])
            self._m_queue_wait.observe(t_slot - t0)
        return True

    def run_direct(self, roots) -> engine.EngineResult:
        """Whole-traversal fast path: run root(s) to completion
        through the plan's compiled program, bypassing the per-tick
        slot machinery (no per-layer host sync, no admission queue).
        Under ``spec.pipeline="persistent"`` (ISSUE 9) the batch is
        ONE Pallas launch — layer loop, direction decision and
        termination in-kernel.  The tick path (`step`) keeps the
        per-layer steps regardless of pipeline: a tick is by
        definition one layer, so ``"persistent"`` ticks run the
        whole-layer megakernel steps instead."""
        return self.compiled.run(roots)

    # -- algorithm portfolio queries (ISSUE 10) -------------------------
    def _semiring_plan(self, algorithm: str):
        """One lazily-built portfolio plan per algorithm, cached on
        the engine; the executable itself is shared process-wide
        through the plan cache (keyed by geometry + resolved spec),
        so many engines over one graph trace each algorithm once."""
        ct = self._semiring_plans.get(algorithm)
        if ct is None:
            from repro.api.plan import plan as _plan
            from repro.api.spec import TraversalSpec
            # a deep bucket/propagation chain (SSSP on a path graph
            # walks one delta bucket per iteration) needs more
            # iterations than a BFS diameter bound; the while_loop
            # exits early, so the generous ceiling costs nothing
            spec = TraversalSpec(
                algorithm=algorithm, policy="topdown",
                max_layers=max(512, self.max_layers))
            ct = self._semiring_plans[algorithm] = _plan(self.fmt,
                                                         spec)
        return ct

    def shortest_paths(self, roots):
        """Single-source shortest paths (min-plus semiring, the
        synthetic symmetric-hash edge weights in [1, 2)) from one
        root (int) or a root batch.  Returns ``(distances, parent)``
        host arrays over the real vertices: ``distances`` float32
        with ``inf`` for unreached vertices, ``parent`` int32 with
        ``-1`` for unreached (the root is its own parent)."""
        ct = self._semiring_plan("sssp")
        res = ct.run(roots)
        self._m_portfolio.inc()
        dist = np.asarray(res.values)[..., :self.n_vertices]
        p = np.asarray(res.state.parent)[..., :self.n_vertices]
        return dist, np.where(np.isfinite(dist), p, -1)

    def components(self):
        """Connected-component labels (min-label propagation run to
        fixpoint).  Returns ``(labels, n_components)``: ``labels`` is
        an int32 host array mapping every real vertex to the smallest
        vertex id in its component."""
        ct = self._semiring_plan("cc")
        res = ct.run(0)       # root is irrelevant: every vertex seeds
        self._m_portfolio.inc()
        labels = np.asarray(res.values)[:self.n_vertices]
        return labels, int(np.unique(labels).size)

    def ksource_depths(self, roots):
        """Batched k-source BFS: one traversal, one depth row per
        root.  Returns the (k, n_vertices) int32 per-source depth
        matrix with ``-1`` for unreached vertices."""
        from repro.algorithms.semiring import INT_INF
        ct = self._semiring_plan("ksource_bfs")
        roots = np.atleast_1d(np.asarray(roots, np.int32))
        res = ct.run_batched(roots)
        self._m_portfolio.inc()
        depths = np.asarray(res.values)[:, :self.n_vertices]
        return np.where(depths >= INT_INF, -1, depths)

    def step(self):
        """One engine tick: advance every active query by one layer.

        When every slot is empty/done after the refill (drain tail,
        or ticking an idle engine) the device ``layer_step`` is NOT
        dispatched — the tick is a host no-op counted in
        ``serve.ticks_skipped``.  Before ISSUE 7 every such tick paid
        a full compiled step for zero active queries."""
        with self._m_tick.time(), \
                span("serve.tick", tick=self._tick_no) as tick_span:
            with span("serve.fill") as fill_span:
                self._expire_queued()
                fill_span.set_metadata(refilled=self._fill_slots())
            n_active = self._active_slots()
            tick_span.set_metadata(active=n_active)
            self._m_occupancy.set(n_active / max(len(self.slots), 1))
            self._set_circuit_gauge()
            if n_active == 0:
                self._m_skipped.inc()
                return
            self._m_ticks.inc()
            tick_no = self._tick_no
            self._tick_no += 1
            with span("serve.dispatch") as dispatch_span:
                dispatch_span.set_metadata(
                    attempts=self._dispatch_with_retry(tick_no))
            if self.injector is not None:
                for s in self.injector.poison_slots(tick_no):
                    if 0 <= s < len(self.slots) \
                            and self.slots[s] is not None \
                            and not self.slots[s].done:
                        # corrupt the slot's parent row the way a bad
                        # device step would: every entry off-by-one,
                        # so parent[root] != root
                        v_pad = self.parent.shape[1]
                        self.parent = self.parent.at[s].set(
                            (jnp.arange(v_pad, dtype=jnp.int32) + 1)
                            % self.n_vertices)
            with span("serve.readback"):
                counts = np.asarray(engine.row_popcounts(self.frontier))
            now = time.perf_counter()
            for i, q in enumerate(self.slots):
                if q is None or q.done:
                    continue
                q.n_layers += 1
                budget = (q.max_layers if q.max_layers is not None
                          else self.max_layers)
                elapsed = now - q.meta.get("submit_t", now)
                if counts[i] == 0:
                    truncated, error = False, None
                elif q.deadline_s is not None \
                        and elapsed > q.deadline_s:
                    truncated, error = True, DeadlineExceeded(
                        f"query uid={q.uid} exceeded its "
                        f"deadline_s={q.deadline_s} after "
                        f"{elapsed:.3f}s / {q.n_layers} layers "
                        f"(partial tree delivered)",
                        uid=q.uid, elapsed_s=elapsed,
                        budget_s=q.deadline_s, where="in_flight")
                elif q.n_layers >= budget:
                    truncated, error = True, None
                else:
                    continue
                with span("serve.harvest", uid=q.uid, layers=q.n_layers):
                    self._harvest(i, q, truncated=truncated, error=error)

    def _harvest_global_budget(self, budget_s: float,
                               elapsed: float) -> None:
        """`run_until_done` budget expiry: deliver every in-flight
        query as a truncated partial (sanity check still applies) and
        every queued query as never-ran — nothing is lost, everything
        is typed."""
        for i, q in enumerate(self.slots):
            if q is not None and not q.done:
                self._harvest(
                    i, q, truncated=True,
                    error=DeadlineExceeded(
                        f"run_until_done budget_s={budget_s} expired "
                        f"after {elapsed:.3f}s with query uid={q.uid} "
                        f"in flight ({q.n_layers} layers done)",
                        uid=q.uid, elapsed_s=elapsed,
                        budget_s=budget_s, where="global"),
                    check=False)
        while self.queue:
            q = self.queue.pop()
            q.error = DeadlineExceeded(
                f"run_until_done budget_s={budget_s} expired after "
                f"{elapsed:.3f}s with query uid={q.uid} still queued",
                uid=q.uid, elapsed_s=elapsed, budget_s=budget_s,
                where="global")
            q.parent = None
            q.truncated = True
            q.done = True
            self.finished.append(q)
            self._m_finished.inc()
            self._m_truncated.inc()
            self._m_deadline.inc()
        self._m_queue.set(0)

    def run_until_done(self, max_ticks: int = 100_000,
                       budget_s: float | None = None) -> int:
        """Drain the queue; returns the number of ticks taken.

        ``budget_s`` is the global wall-clock budget: when it expires,
        in-flight queries are delivered as truncated partials and
        queued ones as never-ran, each carrying a
        `DeadlineExceeded(where="global")` — graceful degradation
        instead of an open-ended run."""
        ticks = 0
        t0 = time.perf_counter()
        while (self.queue or any(q is not None and not q.done
                                 for q in self.slots)):
            elapsed = time.perf_counter() - t0
            if budget_s is not None and elapsed > budget_s:
                self._harvest_global_budget(budget_s, elapsed)
                break
            self.step()
            ticks += 1
            if ticks >= max_ticks:
                now = time.perf_counter()
                slot_report = {}
                for i, q in enumerate(self.slots):
                    if q is None or q.done:
                        continue
                    left = (None if q.deadline_s is None else round(
                        q.deadline_s
                        - (now - q.meta.get("submit_t", now)), 3))
                    slot_report[i] = {
                        "n_layers": q.n_layers,
                        "deadline_remaining_s": left,
                        "retries": q.retries,
                    }
                raise RuntimeError(
                    f"graph serving did not converge within "
                    f"{max_ticks} ticks: queue_depth="
                    f"{len(self.queue)}, active_slots="
                    f"{self._active_slots()}/{len(self.slots)}, "
                    f"per-slot state={slot_report}, "
                    f"max_layers={self.max_layers}, "
                    f"circuit={self.circuit_state()}")
        return ticks
