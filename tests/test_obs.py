"""Unit tests for the obs subsystem (PR 7): span tracer, metrics
registry, instrumented trace_run, the serve tick's profiler spans, and
the cost-drift model probe."""
import contextlib
import glob
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.bfs as bfs
from repro.core import csr as csr_mod
from repro.core import rmat
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       SpanTracer, drift_rows, get_registry,
                       measure_drift, trace_run)
from repro.obs.cost_drift import analytic_layer_bytes
from repro.obs.trace import (LAYER_SPAN, STEP_SPAN, TRAVERSAL_SPAN,
                             xla_profiler)
from repro.serve.graph_engine import BfsQuery, GraphEngine

#: the serve tick's phase spans, each inside a ``serve.tick``
PHASES = ("serve.fill", "serve.dispatch", "serve.readback",
          "serve.harvest")


@pytest.fixture(scope="module")
def g8():
    return csr_mod.from_edges(
        rmat.generate(jax.random.PRNGKey(7), scale=8, edgefactor=8))


# -- SpanTracer -----------------------------------------------------------

def test_span_nesting_and_order():
    tr = SpanTracer()
    with tr.span("outer", kind="o") as o:
        with tr.span("inner"):
            pass
        o.args["amended"] = 1
    assert len(tr) == 2
    inner, outer = tr.spans            # closed innermost-first
    assert inner.name == "inner" and outer.name == "outer"
    # containment: inner lives inside outer's [ts, ts+dur] window
    assert outer.ts_us <= inner.ts_us
    assert inner.ts_us + inner.dur_us <= outer.ts_us + outer.dur_us + 1
    assert outer.args == {"kind": "o", "amended": 1}


def test_chrome_export_parses(tmp_path):
    tr = SpanTracer()
    with tr.span("a"):
        pass
    path = tr.export(str(tmp_path / "t.json"))
    doc = json.loads(open(path).read())
    assert doc["displayTimeUnit"] == "ms"
    meta, ev = doc["traceEvents"]
    assert meta["ph"] == "M" and meta["args"]["name"] == "repro.bfs"
    assert ev == {"name": "a", "cat": "bfs", "ph": "X",
                  "ts": ev["ts"], "dur": ev["dur"],
                  "pid": meta["pid"], "tid": 1, "args": {}}


def test_device_sync_modes():
    x = jnp.ones(4)
    SpanTracer(sync=True).device_sync(x)      # blocks, no error
    SpanTracer(sync=False).device_sync(x)     # no-op


def test_xla_profiler_noop_without_logdir():
    with xla_profiler(None) as ld:
        assert ld is None


# -- metrics --------------------------------------------------------------

def test_counter_monotonic():
    c = Counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_updown():
    g = Gauge("g")
    g.set(5)
    g.dec(2)
    g.inc(0.5)
    assert g.value == 3.5


def test_histogram_exact_and_quantiles():
    h = Histogram("h")
    assert math.isnan(h.percentile(0.5))
    for v in [5, 1, 3, 2, 4]:
        h.observe(v)
    assert (h.count, h.sum, h.min, h.max) == (5, 15.0, 1.0, 5.0)
    assert h.percentile(0.5) == 3.0          # nearest-rank median
    assert h.percentile(0.99) == 5.0
    s = h.summary()
    assert s["count"] == 5 and s["p50"] == 3.0 and s["p99"] == 5.0


def test_histogram_reservoir_slides_but_count_exact():
    h = Histogram("h", reservoir=4)
    for v in range(10):
        h.observe(v)
    assert h.count == 10 and h.min == 0.0 and h.max == 9.0
    assert h.percentile(0.5) >= 6            # window holds 6..9 only


def test_histogram_timer():
    h = Histogram("h")
    with h.time():
        pass
    assert h.count == 1 and h.sum >= 0


def test_registry_get_or_create_and_conflict():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    assert "x" in reg and "y" not in reg
    reg.clear()
    assert "x" not in reg


def test_snapshot_roundtrip_and_prometheus():
    reg = MetricsRegistry()
    reg.counter("a.b").inc(2)
    reg.gauge("c-d").set(1.5)
    reg.histogram("lat").observe(0.25)
    snap = reg.snapshot()
    assert snap == json.loads(json.dumps(snap))
    assert snap["counters"]["a.b"] == 2.0
    assert snap["histograms"]["lat"]["p50"] == 0.25
    prom = reg.to_prometheus()
    assert "# TYPE a_b counter" in prom and "a_b 2" in prom
    assert "c_d 1.5" in prom
    assert 'lat{quantile="0.5"} 0.25' in prom
    assert "lat_count 1" in prom


def test_empty_histogram_snapshot_is_json_safe():
    reg = MetricsRegistry()
    reg.histogram("never")
    snap = reg.snapshot()                    # inf min/max must not leak
    assert snap["histograms"]["never"]["min"] is None
    assert snap["histograms"]["never"]["p99"] is None


def test_default_registry_is_shared():
    assert get_registry() is get_registry()


# -- trace_run ------------------------------------------------------------

def test_trace_run_matches_fused_engine(g8):
    from repro.core.validate import validate

    ct = bfs.plan(g8)
    tr = trace_run(g8, 3)
    ref = ct.run(3)
    assert int(tr.depths) == int(ref.depths)
    # parent ties may break differently between the fused program and
    # the layer tick; both must be valid BFS trees over the same set
    assert np.array_equal(np.asarray(tr.state.visited),
                          np.asarray(ref.state.visited))
    p = bfs.parents_graph500(tr.state, g8.n_vertices)
    assert validate(g8, p, 3).ok
    fused = ct.stats(ref)
    assert len(tr.stats) == len(fused)
    for a, b in zip(tr.stats, fused):
        assert (a.frontier_vertices, a.edges_examined, a.discovered) \
            == (b.frontier_vertices, b.edges_examined, b.discovered)


def test_trace_run_span_contract(g8):
    tr = trace_run(g8, [0, 5])
    names = [s.name for s in tr.tracer.spans]
    assert names.count(TRAVERSAL_SPAN) == 1
    assert names.count(LAYER_SPAN) == len(tr.stats)
    assert names.count(STEP_SPAN) == len(tr.stats)
    assert len(tr.layer_seconds) == len(tr.stats)
    assert all(s >= 0 for s in tr.layer_seconds)
    assert tr.depths.shape == (2,)
    top = [s for s in tr.tracer.spans if s.name == TRAVERSAL_SPAN][0]
    assert top.args["n_roots"] == 2
    assert top.args["n_layers"] == len(tr.stats)


def test_trace_run_reuses_plan_and_tracer(g8):
    ct = bfs.plan(g8)
    tracer = SpanTracer()
    tr = ct.trace_run(0, tracer=tracer)
    assert tr.tracer is tracer and len(tracer) > 0


def _host_events(logdir, prefixes):
    """``(name, start_ns, end_ns, stats)`` of the host-plane events in
    the one profile written under ``logdir`` whose names start with
    one of ``prefixes``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    return [(e.name, e.start_ns, e.end_ns, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith(prefixes)]


def test_span_tracer_spans_reach_the_profiler(g8, tmp_path):
    tr = trace_run(g8, 0, profile_logdir=str(tmp_path))
    names = [e[0] for e in _host_events(tmp_path, ("bfs.",))]
    assert names.count(TRAVERSAL_SPAN) == 1
    assert names.count(LAYER_SPAN) == len(tr.stats)
    assert names.count(STEP_SPAN) == len(tr.stats)


def _serve(g, traced_dir=None):
    """Ten queries over four slots, then one tick with nothing to do;
    under a profiler trace into ``traced_dir`` when given."""
    reg = MetricsRegistry()
    eng = GraphEngine(g, batch_slots=4, registry=reg)
    for uid in range(10):
        eng.submit(BfsQuery(uid=uid, root=(uid * 37) % g.n_vertices))
    with (jax.profiler.trace(traced_dir) if traced_dir
          else contextlib.nullcontext()):
        eng.run_until_done()
        eng.step()
    return eng, reg


@pytest.fixture(scope="module")
def traced_serve(g8, tmp_path_factory):
    logdir = str(tmp_path_factory.mktemp("serve_trace"))
    eng, reg = _serve(g8, logdir)
    return eng, reg, _host_events(logdir, ("serve.",))


def test_serve_tick_spans_on_the_profiler_timeline(traced_serve):
    eng, reg, events = traced_serve
    ticks = [e for e in events if e[0] == "serve.tick"]
    counters = reg.snapshot()["counters"]
    assert len(ticks) == counters["serve.ticks"] \
        + counters["serve.ticks_skipped"]
    assert counters["serve.ticks_skipped"] >= 1
    harvests = [e for e in events if e[0] == "serve.harvest"]
    assert len(harvests) == len(eng.finished) == 10
    assert sorted(e[3]["uid"] for e in harvests) \
        == sorted(q.uid for q in eng.finished)
    layers = {q.uid: q.n_layers for q in eng.finished}
    assert all(e[3]["layers"] == layers[e[3]["uid"]] for e in harvests)
    phases = [e for e in events if e[0] in PHASES]
    assert {e[0] for e in phases} == set(PHASES)
    for name, start, end, _ in phases:
        assert any(t[1] <= start and end <= t[2] for t in ticks), name
    dispatched = [t for t in ticks if t[3]["active"] > 0]
    assert len(dispatched) == counters["serve.ticks"]
    assert sum(e[0] == "serve.dispatch" for e in events) \
        == counters["serve.ticks"]
    assert sum(e[3].get("refilled", 0) for e in events
               if e[0] == "serve.fill") == 10


def test_serve_results_identical_with_spans_recorded(g8, traced_serve):
    traced, _, _ = traced_serve
    plain, _ = _serve(g8)
    by_uid = {q.uid: q for q in plain.finished}
    assert len(by_uid) == len(traced.finished) == 10
    for q in traced.finished:
        other = by_uid[q.uid]
        assert q.n_layers == other.n_layers
        np.testing.assert_array_equal(q.parent, other.parent)


# -- cost drift -----------------------------------------------------------

def test_analytic_layer_bytes_positive(g8):
    from repro.formats import build
    fmt = build(g8, "csr")
    full = analytic_layer_bytes(fmt, pipeline="materialized", tile=None)
    fused = analytic_layer_bytes(fmt, pipeline="fused_gather", tile=256)
    assert full > 0 and fused > 0


def test_measure_drift_and_rows(g8):
    (d,) = measure_drift(g8, pipelines=("fused_gather",))
    assert d.format == "csr" and d.pipeline == "fused_gather"
    assert d.analytic_bytes > 0 and d.compiled_bytes > 0
    assert d.ratio == d.compiled_bytes / d.analytic_bytes
    assert d.hlo_bytes > 0 and d.hlo_ratio > 0
    rows = drift_rows([d])
    assert list(rows) == ["obs.cost_drift.csr.fused_gather"]
    row = rows["obs.cost_drift.csr.fused_gather"]
    assert row["ratio"] == pytest.approx(d.ratio)
    assert row["analytic_bytes"] == d.analytic_bytes
