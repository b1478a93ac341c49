"""TraversalSpec + plan/compile/run API tests (ISSUE 5).

Covers: plan-cache identity and the ≤1-trace-per-(geometry, resolved
spec) guarantee, spec round-trips, deterministic ``"auto"``
resolution (incl. the tile-default-drift regression: plan and the
legacy entries must pick the SAME tile), the single validation home,
legacy shims routing through the plan cache, the distributed spec
path, and the serve engine's deque under a many-query load.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.bfs as bfs
import repro.api.plan as api_plan
from repro.core import csr as csr_mod
from repro.core import engine, rmat
from repro.core.bfs_parallel import parents_graph500
from repro.core.bfs_serial import bfs_serial
from repro.core.validate import validate
from repro.formats import registry
from repro.formats.csr_format import CsrFormat


@pytest.fixture(scope="module")
def g():
    return csr_mod.from_edges(
        rmat.generate(jax.random.PRNGKey(3), scale=9, edgefactor=8))


@pytest.fixture(scope="module")
def g10():
    return csr_mod.from_edges(
        rmat.generate(jax.random.PRNGKey(5), scale=10, edgefactor=8))


def check_oracle(csr, parent_g500, root):
    _, ref_depth = bfs_serial(np.asarray(csr.rows),
                              np.asarray(csr.colstarts),
                              csr.n_vertices, root)
    res = validate(csr, parent_g500, root, reference_depth=ref_depth)
    assert res.ok, res


# ---------------------------------------------------------------------------
# plan -> run correctness
# ---------------------------------------------------------------------------

def test_plan_run_matches_oracle(g):
    ct = bfs.plan(g)
    res = ct.run(17)
    check_oracle(g, np.asarray(parents_graph500(res.state,
                                                g.n_vertices)), 17)
    assert ct.resolved.is_resolved
    assert ct.stats(res)[0].frontier_vertices == 1


def test_plan_run_batched(g):
    roots = [3, 7, 17, 100]
    res = bfs.plan(g).run_batched(roots)
    assert res.state.parent.shape[0] == len(roots)
    for b, root in enumerate(roots):
        st = engine.BfsState(res.state.frontier[b], res.state.visited[b],
                             res.state.parent[b], res.state.layer)
        check_oracle(g, np.asarray(parents_graph500(st, g.n_vertices)),
                     root)


@pytest.mark.parametrize("fmt_name", ["csr", "sell", "bitmap"])
def test_plan_every_format(g, fmt_name):
    fmt = registry.get(fmt_name).from_graph(g)
    res = bfs.plan(fmt, bfs.TraversalSpec(policy="threshold_simd")).run(17)
    check_oracle(g, np.asarray(parents_graph500(res.state,
                                                g.n_vertices)), 17)


def test_plan_batch_width_pads_to_one_trace(g):
    # the executable cache is process-global: drop hits from earlier
    # test modules so the traces counter starts at zero here
    api_plan.clear_cache()
    ct = bfs.plan(g, bfs.TraversalSpec(policy="topdown"), batch=4)
    r1 = ct.run_batched([3, 7])           # padded to 4
    r2 = ct.run_batched([3, 7, 17, 100])  # exactly 4
    assert ct.traces == 1
    assert r1.state.parent.shape[0] == 2
    np.testing.assert_array_equal(np.asarray(r1.state.parent),
                                  np.asarray(r2.state.parent[:2]))
    # the fixed width is a contract, not a hint
    with pytest.raises(ValueError, match="exceeds"):
        ct.run_batched([1, 2, 3, 4, 5])
    with pytest.raises(ValueError, match="at least one root"):
        ct.run_batched([])


# ---------------------------------------------------------------------------
# Plan cache: identity, trace counts, misses
# ---------------------------------------------------------------------------

def test_plan_cache_one_trace_across_many_runs(g):
    api_plan.clear_cache()
    spec = bfs.TraversalSpec(policy="topdown")
    ct = bfs.plan(g, spec)
    for root in range(10):
        ct.run(root)
    assert ct.traces == 1, "re-running one plan must not re-trace"
    # re-planning the same geometry+spec reuses the executable…
    ct2 = bfs.plan(g, spec)
    assert ct2.executable is ct.executable
    ct2.run(11)
    assert ct.traces == 1
    info = api_plan.cache_info()
    assert info["size"] == 1 and info["hits"] == 1


def test_plan_cache_misses_on_spec_and_geometry(g, g10):
    api_plan.clear_cache()
    a = bfs.plan(g, bfs.TraversalSpec(policy="topdown"))
    b = bfs.plan(g, bfs.TraversalSpec(policy="topdown",
                                      pipeline="materialized"))
    c = bfs.plan(g10, bfs.TraversalSpec(policy="topdown"))
    assert a.executable is not b.executable
    assert a.executable is not c.executable
    assert api_plan.cache_info()["size"] == 3


def test_legacy_shims_share_the_plan_cache(g):
    """traverse/traverse_arrays/traverse_format with equal knobs land
    on ONE cached executable — including the same resolved tile (the
    ISSUE 5 tile-default-drift regression: traverse_format used to
    default tile=1, traverse_arrays 1024)."""
    api_plan.clear_cache()
    fmt = CsrFormat.from_csr(g)
    spec = bfs.TraversalSpec(policy="topdown")
    ct = bfs.plan(fmt, spec)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        r1 = engine.traverse(g, 17)
        r2 = engine.traverse_format(fmt, jnp.asarray([17], jnp.int32))
        r3 = engine.traverse_arrays(g.colstarts, g.rows,
                                    jnp.asarray([17], jnp.int32),
                                    n_vertices=g.n_vertices)
    info = api_plan.cache_info()
    assert info["size"] == 1, (
        f"legacy defaults drifted from plan(): {info}")
    assert ct.resolved.tile == fmt.resolve_tile(None)
    np.testing.assert_array_equal(np.asarray(r1.state.parent),
                                  np.asarray(r2.state.parent[0]))
    np.testing.assert_array_equal(np.asarray(r1.state.parent),
                                  np.asarray(r3.state.parent[0]))


def test_loose_knob_form_warns(g):
    with pytest.warns(DeprecationWarning, match="loose-knob"):
        engine.traverse(g, 17, policy=engine.TopDown())
    with pytest.raises(ValueError, match="not both"):
        engine.traverse(g, 17, tile=256,
                        spec=bfs.TraversalSpec(policy="topdown"))


# ---------------------------------------------------------------------------
# Spec: round-trip, determinism, validation
# ---------------------------------------------------------------------------

def test_spec_round_trips_through_dicts(g):
    import json
    for spec in (bfs.TraversalSpec(),
                 bfs.TraversalSpec(policy="beamer", tile=512),
                 bfs.TraversalSpec(
                     policy=engine.PaperLiteralLayers((1, 2)),
                     pipeline="materialized", packed=False,
                     prefetch_depth=2, max_layers=96, merge="owner"),
                 bfs.plan(g).resolved):
        wire = json.loads(json.dumps(spec.to_dict()))
        assert bfs.TraversalSpec.from_dict(wire) == spec


def test_auto_resolution_is_deterministic(g):
    a = bfs.TraversalSpec().resolve(g)
    b = bfs.TraversalSpec().resolve(g)
    assert a == b and a.is_resolved
    # the tile auto is the committed-BENCH-backed format rule
    assert a.tile == CsrFormat.from_csr(g).resolve_tile(None)
    # every field is concrete
    assert all(v != "auto" for v in a.to_dict().values())


def test_spec_validation_rejects_bad_values(g):
    with pytest.raises(ValueError, match="pipeline"):
        bfs.TraversalSpec(pipeline="bogus").validate()
    with pytest.raises(ValueError, match="algorithm"):
        bfs.TraversalSpec(algorithm="scalarish").validate()
    with pytest.raises(ValueError, match="merge"):
        bfs.TraversalSpec(merge="gossip").validate()
    with pytest.raises(ValueError, match="policy"):
        bfs.TraversalSpec(policy="dfs").validate()
    with pytest.raises(ValueError, match="tile"):
        bfs.TraversalSpec(tile=0).validate()
    with pytest.raises(ValueError, match="prefetch_depth"):
        bfs.TraversalSpec(prefetch_depth=-1).validate()
    with pytest.raises(ValueError, match="max_layers"):
        bfs.TraversalSpec(max_layers=0).validate()
    with pytest.raises(ValueError, match="unknown TraversalSpec"):
        bfs.TraversalSpec.from_dict({"tiles": 4})


def test_prefetch_on_bitmap_rejected_in_one_place(g):
    fmt = registry.get("bitmap").from_graph(g)
    spec = bfs.TraversalSpec(prefetch_depth=2)
    with pytest.raises(ValueError, match="bitmap"):
        spec.resolve(fmt)
    with pytest.raises(ValueError, match="bitmap"):
        bfs.plan(fmt, spec)
    # …and the same spec is fine on a streamed layout
    bfs.plan(CsrFormat.from_csr(g), spec).run(17)


def test_policy_string_names_resolve(g):
    for name, cls in bfs.POLICIES.items():
        r = bfs.TraversalSpec(policy=name).resolve(g)
        assert isinstance(r.policy, cls)


def test_make_steps_requires_resolved_spec(g):
    fmt = CsrFormat.from_csr(g)
    with pytest.raises(ValueError, match="resolve"):
        fmt.make_steps(bfs.TraversalSpec())      # 'auto' fields left
    fmt.make_steps(bfs.TraversalSpec().resolve(fmt))   # fine


def test_merge_flavour_shares_single_chip_executable(g):
    """merge is mesh-only: specs differing only in merge must share
    one single-chip trace."""
    api_plan.clear_cache()
    a = bfs.plan(g, bfs.TraversalSpec(policy="topdown",
                                      merge="allreduce"))
    b = bfs.plan(g, bfs.TraversalSpec(policy="topdown", merge="owner"))
    assert a.executable is b.executable
    assert api_plan.cache_info()["size"] == 1


def test_mesh_bound_plan_rejects_single_chip_surfaces(g):
    mesh = jax.make_mesh((1,), ("x",))
    ct = bfs.plan(g, mesh=mesh)
    # the single-layer tick is the one single-chip surface left: the
    # mesh program runs whole searches (run, run_batched, lower)
    state = engine.BfsState(*engine._init_batched(
        jnp.asarray([3], jnp.int32), g.n_vertices, g.n_vertices_padded),
        jnp.int32(0))
    with pytest.raises(NotImplementedError):
        ct.layer_step(state)
    ct.lower([3, 7]).compile()
    res = ct.run_batched([3, 7])
    assert res.state.parent.shape[0] == 2 and res.stats is None
    for i, root in enumerate((3, 7)):
        check_oracle(g, np.asarray(parents_graph500(res.state,
                                                    g.n_vertices))[i],
                     root)
    # fields the fixed per-chip program cannot honor are flagged…
    with pytest.warns(UserWarning, match="ignored"):
        bfs.plan(g, bfs.TraversalSpec(pipeline="materialized"),
                 mesh=mesh)
    # …but a fully-resolved spec passes silently (its concrete fields
    # are resolution artifacts, not user intent)
    resolved = bfs.plan(g).resolved
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        bfs.plan(g, resolved, mesh=mesh)


# ---------------------------------------------------------------------------
# layer_step + serve engine (deque under many-query load)
# ---------------------------------------------------------------------------

def test_compiled_layer_step_advances_one_layer(g):
    """Ticking layer_step to exhaustion yields a valid tree with the
    same reached set as the whole-search run (parent identities may
    differ: the tick is the SIMD step, the TopDown run the scalar
    one)."""
    ct = bfs.plan(g, bfs.TraversalSpec(policy="topdown"))
    full = ct.run(17)
    f, v, p = engine._init_batched(jnp.asarray([17], jnp.int32),
                                   g.n_vertices, g.n_vertices_padded)
    st = engine.BfsState(f, v, p, jnp.int32(0))
    for _ in range(int(full.state.layer)):
        st = ct.layer_step(st)
    assert int(st.layer) == int(full.state.layer)
    got = np.asarray(st.parent[0][:g.n_vertices])
    ref = np.asarray(full.state.parent[:g.n_vertices])
    np.testing.assert_array_equal(got < g.n_vertices,
                                  ref < g.n_vertices)
    check_oracle(g, np.where(got >= g.n_vertices, -1, got), 17)


def test_serve_engine_spec_and_deque_many_queries(g):
    from repro.serve.graph_engine import BfsQuery, GraphEngine
    eng = GraphEngine(g, batch_slots=4, spec=bfs.TraversalSpec())
    # the tick is policy-free: an explicitly-set policy is flagged,
    # the neutral topdown (name or object) is not
    with pytest.warns(UserWarning, match="policy-free"):
        GraphEngine(g, batch_slots=2,
                    spec=bfs.TraversalSpec(policy="beamer"))
    with warnings.catch_warnings():
        warnings.simplefilter("error", UserWarning)
        GraphEngine(g, batch_slots=2,
                    spec=bfs.TraversalSpec(policy="topdown"))
    n = 40                         # >> slots: continuous refill churn
    for uid in range(n):
        eng.submit(BfsQuery(uid=uid, root=uid % 64))
    eng.run_until_done()
    assert len(eng.finished) == n
    assert not eng.queue
    by_uid = {q.uid: q for q in eng.finished}
    assert set(by_uid) == set(range(n))
    # same root => same tree, regardless of slot/tick interleaving
    ref = {}
    for uid, q in by_uid.items():
        r = uid % 64
        if r in ref:
            np.testing.assert_array_equal(q.parent, ref[r])
        else:
            ref[r] = q.parent
    check_oracle(g, by_uid[3].parent, 3)
    # the engine stores ONE CompiledTraversal, not loose attributes
    assert eng.compiled.resolved is eng.resolved
    assert eng.algorithm == "simd" and eng.max_layers == 64


def test_distributed_spec_path_matches_legacy(g):
    from repro.core.bfs_distributed import run_bfs_distributed
    mesh = jax.make_mesh((1,), ("x",))
    p_spec, l_spec = run_bfs_distributed(
        g, 11, mesh, spec=bfs.TraversalSpec())
    p_leg, l_leg = run_bfs_distributed(g, 11, mesh, merge="packed")
    np.testing.assert_array_equal(np.asarray(p_spec), np.asarray(p_leg))
    assert int(l_spec) == int(l_leg)
    with pytest.raises(ValueError, match="not both"):
        run_bfs_distributed(g, 11, mesh, merge="owner",
                            spec=bfs.TraversalSpec())
    # fields the fixed per-chip program cannot honor are flagged
    with pytest.warns(UserWarning, match="ignored"):
        run_bfs_distributed(g, 11, mesh,
                            spec=bfs.TraversalSpec(packed=False))


def test_plan_takes_only_a_multi_device_mesh_from_context(g):
    """Under ``jax.set_mesh`` of one device, and outside any mesh
    context, ``plan(g)`` is the single-chip plan it always was."""
    outside = bfs.plan(g)
    with jax.set_mesh(jax.make_mesh((1,), ("x",))):
        inside = bfs.plan(g)
    for ct in (outside, inside):
        assert ct.mesh is None and ct._partition is None
        assert ct.executable is outside.executable
    assert inside.resolved == outside.resolved
    p = np.asarray(parents_graph500(inside.run(17).state, g.n_vertices))
    check_oracle(g, p, 17)


def test_plan_mesh_binding_routes_distributed(g):
    mesh = jax.make_mesh((1,), ("x",))
    ct = bfs.plan(g, mesh=mesh)
    assert ct.executable is None and ct.traces == 0
    parent, layers = ct.run(11)
    assert ct._partition is not None
    p2, _ = ct.run(11)            # partition reused, same result
    np.testing.assert_array_equal(np.asarray(parent), np.asarray(p2))
    p = np.asarray(parent)
    # the distributed tree resolves parents by min (deterministic), the
    # single-chip engine by racy scatter — compare the reached set and
    # validate the tree, not parent identities
    ref = bfs.plan(g, bfs.TraversalSpec(policy="topdown")).run(11)
    ref_p = np.asarray(ref.state.parent[:g.n_vertices])
    np.testing.assert_array_equal(p < g.n_vertices,
                                  ref_p < g.n_vertices)
    check_oracle(g, np.where(p >= g.n_vertices, -1, p), 11)
