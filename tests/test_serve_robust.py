"""Serve-tier robustness (ISSUE 8): admission control, deadlines,
fault injection, recovery.

The chaos contract: under injected device failures, stalls and
poisoned results, the engine delivers every submitted query exactly
once, never delivers a corrupted tree, and every degraded outcome is
typed (`QueueFullError`, `AdmissionRejected`, `DeadlineExceeded`,
`TickRetriesExhausted`) and counted (``serve.retries``,
``serve.requeued``, ``serve.poisoned``, ``serve.rejected``,
``serve.deadline_exceeded``, ``serve.circuit_state``).
"""
import time

import jax
import numpy as np
import pytest

import repro.bfs as bfs
from repro.core.csr import from_edges
from repro.core.rmat import generate
from repro.core.validate import validate
from repro.errors import (AdmissionRejected, DeadlineExceeded,
                          InjectedFault, QueueFullError,
                          TickRetriesExhausted)
from repro.obs.metrics import MetricsRegistry
from repro.serve import robust
from repro.serve.graph_engine import BfsQuery, GraphEngine

CSR = from_edges(generate(jax.random.PRNGKey(3), scale=7, edgefactor=6))
V = CSR.n_vertices


def _path_csr(n=64):
    """0-1-2-...-(n-1): one layer per tick, n-1 layers from root 0 —
    the deterministic long-running query for deadline tests."""
    import jax.numpy as jnp
    from repro.core.rmat import EdgeList
    src = jnp.asarray(list(range(n - 1)) + list(range(1, n)), jnp.int32)
    dst = jnp.asarray(list(range(1, n)) + list(range(n - 1)), jnp.int32)
    return from_edges(EdgeList(src=src, dst=dst, n_vertices=n))


def _engine(**kw):
    kw.setdefault("registry", MetricsRegistry())
    kw.setdefault("batch_slots", 4)
    kw.setdefault("retry_backoff_s", 0.001)
    graph = kw.pop("graph", CSR)
    return GraphEngine(graph, **kw)


# -- robust primitives ------------------------------------------------------
def test_backoff_is_capped_exponential():
    assert robust.backoff_s(0, base=0.01, cap=1.0) == 0.01
    assert robust.backoff_s(3, base=0.01, cap=1.0) == 0.08
    assert robust.backoff_s(30, base=0.01, cap=0.25) == 0.25


def test_admission_queue_priority_then_fifo():
    q = robust.AdmissionQueue(capacity=8)
    assert not q and len(q) == 0
    q.push("a", 0)
    q.push("b", 5)
    q.push("c", 0)
    q.push("d", 5)
    assert [q.pop() for _ in range(4)] == ["b", "d", "a", "c"]


def test_admission_queue_capacity_and_force():
    q = robust.AdmissionQueue(capacity=2)
    assert q.push(1) and q.push(2)
    assert q.full
    assert not q.push(3)          # refused, not enqueued
    assert len(q) == 2
    assert q.push(4, force=True)  # recovery path bypasses the bound
    assert len(q) == 3


def test_admission_queue_remove_if():
    q = robust.AdmissionQueue(capacity=8)
    for i in range(6):
        q.push(i, priority=i % 2)
    evens = q.remove_if(lambda x: x % 2 == 0)
    assert sorted(evens) == [0, 2, 4]
    assert sorted(q.items()) == [1, 3, 5]


def test_admission_policy_validates():
    with pytest.raises(ValueError):
        robust.AdmissionPolicy(queue_capacity=0, degraded_depth=1)
    with pytest.raises(ValueError):
        robust.AdmissionPolicy(queue_capacity=4, degraded_depth=-1)


def test_injector_fires_once_per_trigger():
    inj = robust.ServeFaultInjector(fail_ticks=(2,), slow_ticks=(1,),
                                    slow_s=0.5, poison=((3, 0),))
    assert inj.faults_remaining == 3
    inj.check_tick(0)                      # not scheduled: no raise
    assert inj.stall_s(1) == 0.5
    assert inj.stall_s(1) == 0.0           # fired
    with pytest.raises(InjectedFault):
        inj.check_tick(2)
    inj.check_tick(2)                      # fired: no raise
    assert inj.poison_slots(3) == (0,)
    assert inj.poison_slots(3) == ()
    assert inj.faults_remaining == 0


# -- admission control ------------------------------------------------------
def test_bounded_queue_rejects_typed():
    reg = MetricsRegistry()
    eng = _engine(batch_slots=2, queue_capacity=3, registry=reg)
    admitted = 0
    for i in range(9):
        try:
            d = eng.submit(BfsQuery(uid=i, root=i))
            assert d.admitted
            admitted += 1
        except QueueFullError as e:
            assert isinstance(e, AdmissionRejected)
            assert e.decision is not None
            assert e.decision.circuit == robust.CIRCUIT_SHEDDING
            assert "capacity" in e.decision.reason
    assert admitted == 3
    snap = reg.snapshot()
    assert snap["counters"]["serve.rejected"] == 6
    assert snap["gauges"]["serve.circuit_state"] \
        == robust.CIRCUIT_CODES[robust.CIRCUIT_SHEDDING]
    eng.run_until_done()
    assert len(eng.finished) == 3
    assert eng.metrics.gauge("serve.circuit_state").value \
        == robust.CIRCUIT_CODES[robust.CIRCUIT_HEALTHY]


def test_priority_shedding_when_degraded():
    pol = robust.AdmissionPolicy(queue_capacity=64, degraded_depth=2,
                                 shed_min_priority=5)
    eng = _engine(batch_slots=1, admission=pol)
    # saturate: 1 slot + queue past degraded_depth
    for i in range(4):
        eng.submit(BfsQuery(uid=i, root=i))
    eng.step()   # fills the slot -> occupancy 1.0, queue depth 3
    assert eng.circuit_state() == robust.CIRCUIT_DEGRADED
    with pytest.raises(AdmissionRejected) as ei:
        eng.submit(BfsQuery(uid=90, root=1, priority=0))
    assert not isinstance(ei.value, QueueFullError)
    assert "shedding" in ei.value.decision.reason
    # important traffic still gets through
    d = eng.submit(BfsQuery(uid=91, root=2, priority=9))
    assert d.admitted
    eng.run_until_done()
    assert {q.uid for q in eng.finished} == {0, 1, 2, 3, 91}


def test_priority_order_drains_high_first():
    eng = _engine(batch_slots=1)
    eng.submit(BfsQuery(uid=0, root=0))          # fills the slot
    eng.step()
    lo = BfsQuery(uid=1, root=1, priority=0)
    hi = BfsQuery(uid=2, root=2, priority=3)
    eng.submit(lo)
    eng.submit(hi)
    eng.run_until_done()
    uids = [q.uid for q in eng.finished]
    assert uids.index(2) < uids.index(1)


# -- deadlines --------------------------------------------------------------
def test_queued_deadline_expires_without_running():
    eng = _engine(batch_slots=1)
    eng.submit(BfsQuery(uid=0, root=0))
    q = BfsQuery(uid=1, root=1, deadline_s=0.0)
    eng.submit(q)
    time.sleep(0.005)
    eng.run_until_done()
    assert q.done and q.truncated and q.parent is None
    assert isinstance(q.error, DeadlineExceeded)
    assert q.error.where == "queued"
    assert q.error.uid == 1


def test_in_flight_deadline_returns_partial():
    eng = _engine(batch_slots=1, graph=_path_csr(64),
                  spec=bfs.TraversalSpec(max_layers=200))
    # warm the jit cache first so the deadline isn't eaten by compile
    warm = BfsQuery(uid=99, root=0)
    eng.submit(warm)
    eng.run_until_done()
    q = BfsQuery(uid=0, root=0, deadline_s=0.05)
    eng.submit(q)
    eng.step()   # fills the slot, runs layer 1 (well under deadline)
    assert not q.done
    time.sleep(0.06)
    eng.step()   # deadline tripped mid-traversal
    assert q.done and q.truncated
    assert isinstance(q.error, DeadlineExceeded)
    assert q.error.where == "in_flight"
    assert q.parent is not None and int(q.parent[0]) == 0
    assert q.n_layers < 63          # genuinely partial
    assert eng.metrics.snapshot()["counters"][
        "serve.deadline_exceeded"] == 1


def test_per_query_layer_budget_overrides_spec():
    eng = _engine(batch_slots=1)
    q = BfsQuery(uid=0, root=0, max_layers=1)
    eng.submit(q)
    eng.run_until_done()
    assert q.truncated and q.n_layers == 1
    assert q.error is None       # layer truncation is budget, not error


def test_global_budget_harvests_everything():
    eng = _engine(batch_slots=2)
    qs = [BfsQuery(uid=i, root=i) for i in range(6)]
    for q in qs:
        eng.submit(q)
    eng.run_until_done(budget_s=0.0)
    assert all(q.done for q in qs)
    assert len(eng.finished) == 6
    assert not eng.queue
    for q in qs:
        assert isinstance(q.error, DeadlineExceeded)
        assert q.error.where == "global"


# -- fault injection / recovery ---------------------------------------------
def test_injected_failures_retry_and_lose_nothing():
    reg = MetricsRegistry()
    inj = robust.ServeFaultInjector(fail_ticks=(0, 2, 5))
    eng = _engine(registry=reg, injector=inj)
    qs = [BfsQuery(uid=i, root=(i * 11) % V) for i in range(10)]
    for q in qs:
        eng.submit(q)
    eng.run_until_done()
    assert len(eng.finished) == 10
    assert {q.uid for q in eng.finished} == set(range(10))
    assert inj.faults_remaining == 0
    snap = reg.snapshot()["counters"]
    assert snap["serve.retries"] == 3
    for q in qs:
        assert not q.truncated and q.error is None
        assert validate(CSR, q.parent, q.root).ok


def test_poisoned_result_never_delivered():
    reg = MetricsRegistry()
    inj = robust.ServeFaultInjector(poison=((0, 0), (1, 2)))
    eng = _engine(registry=reg, injector=inj)
    qs = [BfsQuery(uid=i, root=i) for i in range(8)]
    for q in qs:
        eng.submit(q)
    eng.run_until_done()
    assert len(eng.finished) == 8
    snap = reg.snapshot()["counters"]
    assert snap["serve.poisoned"] == 2
    assert snap["serve.requeued"] == 2
    for q in qs:
        assert validate(CSR, q.parent, q.root).ok
    poisoned = [q for q in qs if q.retries > 0]
    assert len(poisoned) == 2


def test_retry_exhaustion_requeues_then_raises_typed():
    # a listed tick fires only once (retries then succeed), so retry
    # exhaustion needs an injector that fails tick 0 unconditionally
    class AlwaysFail(robust.ServeFaultInjector):
        def check_tick(self, tick):
            if tick == 0:
                raise InjectedFault("tick 0 always fails")
    eng = _engine(injector=AlwaysFail(), max_tick_retries=2)
    qs = [BfsQuery(uid=i, root=i) for i in range(4)]
    for q in qs:
        eng.submit(q)
    with pytest.raises(TickRetriesExhausted) as ei:
        eng.step()
    assert isinstance(ei.value, RuntimeError)
    assert isinstance(ei.value.__cause__, InjectedFault)
    # nothing lost: the in-flight queries went back to the queue...
    assert len(eng.queue) == 4
    assert all(q.retries == 1 for q in qs)
    # ...and a later drain (tick 0 is past) delivers all of them
    eng.run_until_done()
    assert {q.uid for q in eng.finished} == {0, 1, 2, 3}
    for q in qs:
        assert validate(CSR, q.parent, q.root).ok


def test_slow_tick_trips_deadline():
    inj = robust.ServeFaultInjector(slow_ticks=(0,), slow_s=0.05)
    eng = _engine(batch_slots=1, graph=_path_csr(64),
                  spec=bfs.TraversalSpec(max_layers=200),
                  injector=inj)
    q = BfsQuery(uid=0, root=0, deadline_s=0.02)
    eng.submit(q)
    eng.run_until_done()
    assert q.done and q.truncated
    assert isinstance(q.error, DeadlineExceeded)
    assert q.error.where == "in_flight"


def test_nonconvergence_report_carries_slot_state():
    eng = _engine(batch_slots=2)
    eng.submit(BfsQuery(uid=0, root=0, deadline_s=120.0))
    eng.submit(BfsQuery(uid=1, root=1))
    with pytest.raises(RuntimeError) as ei:
        eng.run_until_done(max_ticks=1)
    msg = str(ei.value)
    assert "deadline_remaining_s" in msg
    assert "retries" in msg
    assert "circuit=" in msg


def test_vmem_fallback_degrade_is_observable():
    """The packed->dense planner fallback is no longer silent: it
    counts ``serve.degrade.vmem_fallback`` and lands in the degrade
    log with the budget that failed.  ``eval_shape`` exercises the
    real trace-time decision without allocating the giant arrays."""
    import jax.numpy as jnp

    from repro.core import bitmap as bm
    from repro.core import engine as core_engine
    from repro.obs.metrics import (clear_degrade_log, degrade_log,
                                   get_registry)
    clear_degrade_log()
    reg = get_registry()
    before = reg.counter("serve.degrade.vmem_fallback").value
    v_pad = 131072
    n_batch = 128   # 128 x 128Ki x 4B = 64 MiB >> the 12 MiB budget
    words = jax.ShapeDtypeStruct(
        (n_batch, v_pad // bm.BITS_PER_WORD), jnp.uint32)
    colstarts = jax.ShapeDtypeStruct((v_pad + 1,), jnp.int32)
    jax.eval_shape(
        lambda cs, aw: core_engine.plan_active_tiles_batched(
            cs, aw, v_pad, tile=1024,
            n_blocks=8, packed=True),
        colstarts, words)
    assert reg.counter("serve.degrade.vmem_fallback").value \
        == before + 1
    events = [e for e in degrade_log() if e.site == "vmem_fallback"]
    assert events, "no DegradeEvent recorded"
    assert "VMEM budget" in events[-1].reason
    assert "dense" in events[-1].fallback
    clear_degrade_log()


# -- query lifecycle --------------------------------------------------------
def _histogram(reg, name):
    return reg.snapshot()["histograms"][name]


def test_caller_arrival_time_and_the_latency_split():
    reg = MetricsRegistry()
    eng = _engine(registry=reg, batch_slots=1)
    arrival = time.perf_counter() - 5.0
    late = BfsQuery(uid=0, root=0, meta={"submit_t": arrival})
    queued = BfsQuery(uid=1, root=1)
    eng.submit(late)
    eng.submit(queued)
    eng.run_until_done()
    # the caller's arrival time is kept: latency runs from it
    assert late.meta["submit_t"] == arrival
    assert late.meta["latency_s"] >= 5.0
    assert _histogram(reg, "serve.query_latency_s")["max"] >= 5.0
    for q in (late, queued):
        m = q.meta
        assert m["submit_t"] <= m["slot_t"] <= m["harvest_t"]
        assert (m["slot_t"] - m["submit_t"]) + (m["harvest_t"]
                                                - m["slot_t"]) \
            == pytest.approx(m["latency_s"], abs=1e-9)
    # the second query waited for the one slot
    assert queued.meta["slot_t"] >= late.meta["harvest_t"]
    waits = _histogram(reg, "serve.queue_wait_s")
    in_slot = _histogram(reg, "serve.in_slot_s")
    latency = _histogram(reg, "serve.query_latency_s")
    assert waits["count"] == in_slot["count"] == latency["count"] == 2
    assert waits["sum"] + in_slot["sum"] \
        == pytest.approx(latency["sum"], abs=1e-9)


def test_requeued_query_gets_a_new_slot_time():
    reg = MetricsRegistry()
    inj = robust.ServeFaultInjector(poison=((0, 0),))
    eng = _engine(registry=reg, batch_slots=1, injector=inj)
    q = BfsQuery(uid=0, root=0)
    eng.submit(q)
    eng.step()
    first = q.meta["slot_t"]
    eng.run_until_done()
    assert q.retries == 1 and validate(CSR, q.parent, q.root).ok
    assert q.meta["slot_t"] > first
    # only the delivered run is observed, from its own slot fill
    in_slot = _histogram(reg, "serve.in_slot_s")
    assert in_slot["count"] == 1
    assert in_slot["sum"] == pytest.approx(
        q.meta["harvest_t"] - q.meta["slot_t"], abs=1e-9)
    assert _histogram(reg, "serve.queue_wait_s")["sum"] == pytest.approx(
        q.meta["slot_t"] - q.meta["submit_t"], abs=1e-9)


def test_finished_queries_are_exactly_once():
    """No duplicate delivery under mixed injection."""
    inj = robust.ServeFaultInjector(fail_ticks=(1,), poison=((0, 1),))
    eng = _engine(injector=inj)
    for i in range(12):
        eng.submit(BfsQuery(uid=i, root=(i * 5) % V))
    eng.run_until_done()
    uids = [q.uid for q in eng.finished]
    assert sorted(uids) == list(range(12))
    assert len(set(uids)) == 12
