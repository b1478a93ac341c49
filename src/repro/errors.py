"""Typed failure taxonomy — the serve/plan tier's error contract.

The ROADMAP's north-star serve tier ("heavy traffic from millions of
users") needs failures that are *classifiable at the call site*: an
operator script must be able to distinguish "your graph is malformed"
(client bug, never retry) from "the queue is full" (backpressure,
retry later) from "your query ran out of budget" (partial result,
decide) from "the device step failed" (infrastructure, the engine
already retried).  Python's builtin exceptions can't carry that
taxonomy, so every failure the BFS plan/serve path raises or attaches
derives from `ReproError`:

    ReproError
    ├── GraphValidationError   (also ValueError)   admission-time input
    ├── KernelRefusedError     (also ValueError)   no TPU lowering
    ├── AdmissionRejected                          load-shed at submit
    │   └── QueueFullError                         bounded-queue overflow
    ├── DeadlineExceeded                           query budget expired
    ├── InjectedFault          (also RuntimeError) chaos-test fault
    └── TickRetriesExhausted   (also RuntimeError) retry budget spent

Design rules:

* **Dual inheritance keeps old callers working.**
  `GraphValidationError` IS a `ValueError` — code that guarded
  ``plan()`` with ``except ValueError`` still catches it, while new
  code can catch the precise class.  Likewise `InjectedFault` /
  `TickRetriesExhausted` are `RuntimeError`\\ s.
* **Errors are data.** `DeadlineExceeded` is *attached* to a
  truncated query result (``BfsQuery.error``) rather than raised from
  the tick loop — a deadline miss is a degraded result to deliver,
  not a serving failure; see `repro.serve.graph_engine`.
* **This module is import-leaf.**  It depends on nothing inside the
  package so every layer (kernels, formats, api, serve) can raise
  typed errors without import cycles.
"""
from __future__ import annotations


class ReproError(Exception):
    """Base class of every typed failure this package raises."""


class GraphValidationError(ReproError, ValueError):
    """A graph (or root) failed admission-time structural validation.

    Raised by ``repro.bfs.plan`` / `GraphEngine` construction /
    ``submit`` when the input could produce a *wrong answer* rather
    than an error: non-monotone ``colstarts``, out-of-range neighbor
    ids, wrong dtypes, NaN-shaped geometry, roots outside ``[0, V)``.
    The message always names the violated invariant and the fix.
    """


class KernelRefusedError(ReproError, ValueError):
    """A plan asked, on a TPU backend, for a pipeline whose Pallas
    kernels the TPU compiler refuses.

    Raised by `TraversalSpec.validate` at plan time, before anything
    is traced; the message quotes the compiler's refusal
    (`repro.kernels.TPU_REFUSALS`) and names the pipeline the format
    does compile (``GraphFormat.tpu_pipelines``).  ``auto`` never
    resolves to such a pipeline.
    """


class AdmissionRejected(ReproError):
    """The serve tier declined to enqueue a query (load shedding).

    Carries the `repro.serve.robust.AdmissionDecision` that rejected
    it as ``decision`` — the typed record of *why* (circuit state,
    queue depth) for the client's retry policy.
    """

    def __init__(self, message: str, decision=None):
        super().__init__(message)
        self.decision = decision


class QueueFullError(AdmissionRejected):
    """The engine's bounded submit queue is at capacity.

    The backpressure signal the ISSUE-8 admission control emits
    *instead of* unbounded queue growth (or a silently-dropping
    ``deque(maxlen=...)``): the client sees the rejection and can
    retry after draining, with jitter, or route elsewhere.
    """


class DeadlineExceeded(ReproError):
    """A query's wall-clock (or global run) budget expired.

    Attached to the harvested `BfsQuery` as ``query.error`` with
    ``truncated=True`` — the parent array, when present, is PARTIAL.

    Attributes:
      uid: the query's uid (None for engine-global budgets).
      elapsed_s: wall seconds from submit when the budget tripped.
      budget_s: the configured budget.
      where: ``"queued"`` (expired before ever running),
        ``"in_flight"`` (expired mid-traversal) or ``"global"``
        (the `run_until_done` budget harvested it).
    """

    def __init__(self, message: str, *, uid=None, elapsed_s=None,
                 budget_s=None, where: str = "in_flight"):
        super().__init__(message)
        self.uid = uid
        self.elapsed_s = elapsed_s
        self.budget_s = budget_s
        self.where = where


class InjectedFault(ReproError, RuntimeError):
    """A `repro.serve.robust.ServeFaultInjector` fired.

    The serve-path sibling of `repro.runtime.fault.SimulatedFailure`:
    raised from inside the engine tick to prove the retry/requeue
    machinery recovers (chaos tests kill ticks mid-run and assert
    zero lost queries).
    """


class TickRetriesExhausted(ReproError, RuntimeError):
    """A serve tick kept failing past the capped-backoff retry budget.

    Before raising, the engine re-queues every in-flight query (their
    state restarts from the root), so even this terminal path loses
    nothing — a later `run_until_done` drains them.
    """
