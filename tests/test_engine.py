"""Oracle-equivalence tests for the unified traversal engine.

Every direction policy and the batched multi-root path must produce a
valid BFS tree with depths equal to the serial oracle (Algorithm 1) —
on an RMAT graph and on adversarial shapes (star: maximal §3.3.2 word
collisions; path: maximal layer count; disconnected: unreachable
component) — plus the serve engine and fused/hostloop agreement.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitmap as bm
from repro.core import csr as csr_mod
from repro.core import engine, rmat
from repro.core.bfs_parallel import parents_graph500
from repro.core.bfs_serial import bfs_serial
from repro.core.rmat import EdgeList
from repro.core.validate import validate
from repro.serve.graph_engine import BfsQuery, GraphEngine

POLICIES = [
    engine.TopDown(),
    engine.ThresholdSimd(2048),
    engine.PaperLiteralLayers((1, 2)),
    engine.BeamerHybrid(),
]


def _csr_from_pairs(pairs, n):
    src = jnp.asarray([a for a, b in pairs] + [b for a, b in pairs],
                      jnp.int32)
    dst = jnp.asarray([b for a, b in pairs] + [a for a, b in pairs],
                      jnp.int32)
    return csr_mod.from_edges(EdgeList(src, dst, n))


def star_graph(n=128):
    """Hub 0 <-> 1..n-1: every discovery lands in one layer and
    collides inside 4 bitmap words (the Fig. 6 race, maximized)."""
    return _csr_from_pairs([(0, i) for i in range(1, n)], n)


def path_graph(n=96):
    """A chain: one vertex per layer — maximal layer count."""
    return _csr_from_pairs([(i, i + 1) for i in range(n - 1)], n)


def disconnected_graph(n=128):
    """Two components: a clique-ish star [0, n/2) and a path [n/2, n)."""
    half = n // 2
    pairs = [(0, i) for i in range(1, half)]
    pairs += [(i, i + 1) for i in range(half, n - 1)]
    return _csr_from_pairs(pairs, n)


GRAPHS = {
    "rmat10": lambda: csr_mod.from_edges(
        rmat.generate(jax.random.PRNGKey(3), scale=10, edgefactor=16)),
    "star": star_graph,
    "path": path_graph,
    "disconnected": disconnected_graph,
}


@pytest.fixture(scope="module")
def graphs():
    return {k: v() for k, v in GRAPHS.items()}


def check_oracle(csr, parent_g500, root):
    _, ref_depth = bfs_serial(np.asarray(csr.rows),
                              np.asarray(csr.colstarts),
                              csr.n_vertices, root)
    res = validate(csr, parent_g500, root, reference_depth=ref_depth)
    assert res.ok, res


@pytest.mark.parametrize("policy", POLICIES,
                         ids=lambda p: type(p).__name__)
@pytest.mark.parametrize("graph_name", list(GRAPHS))
def test_every_policy_matches_oracle(graphs, graph_name, policy):
    g = graphs[graph_name]
    root = 0 if graph_name != "rmat10" else 17
    res = engine.traverse(g, root, policy=policy, max_layers=128)
    check_oracle(g, np.asarray(parents_graph500(res.state,
                                                g.n_vertices)), root)


def test_path_graph_runs_one_layer_per_vertex(graphs):
    g = graphs["path"]
    res = engine.traverse(g, 0, max_layers=128)
    # 96 expansions: one per frontier {0}..{95}, the last discovers
    # nothing and empties the frontier
    assert int(res.state.layer) == 96
    assert int(res.depths) == 96


def test_disconnected_component_unreached(graphs):
    g = graphs["disconnected"]
    res = engine.traverse(g, 0)
    p = np.asarray(parents_graph500(res.state, g.n_vertices))
    assert (p[64:] == -1).all(), "other component must stay unreached"
    check_oracle(g, p, 0)


@pytest.mark.parametrize("policy", POLICIES,
                         ids=lambda p: type(p).__name__)
def test_batched_multiroot_matches_oracle(graphs, policy):
    g = graphs["rmat10"]
    roots = [3, 7, 11, 100, 511, 900, 42, 42]   # dup roots are legal
    res = engine.traverse(g, roots, policy=policy)
    assert res.state.parent.shape[0] == len(roots)
    for b, root in enumerate(roots):
        st = engine.BfsState(res.state.frontier[b], res.state.visited[b],
                             res.state.parent[b], res.state.layer)
        check_oracle(g, np.asarray(parents_graph500(st, g.n_vertices)),
                     root)


def test_batched_multiroot_adversarial(graphs):
    g = graphs["disconnected"]
    roots = [0, 64, 1, 127]          # both components, both directions
    res = engine.traverse(g, roots, policy=engine.ThresholdSimd(64))
    for b, root in enumerate(roots):
        st = engine.BfsState(res.state.frontier[b], res.state.visited[b],
                             res.state.parent[b], res.state.layer)
        check_oracle(g, np.asarray(parents_graph500(st, g.n_vertices)),
                     root)


def test_batched_depths_match_singles(graphs):
    g = graphs["rmat10"]
    roots = [3, 7, 900]
    res = engine.traverse(g, roots)
    for b, root in enumerate(roots):
        single = engine.traverse(g, root)
        assert int(res.depths[b]) == int(single.depths)


def test_fused_matches_hostloop(graphs):
    g = graphs["rmat10"]
    fused = engine.traverse(g, 17, policy=engine.BeamerHybrid())
    host_state, _, host_log = engine.traverse_hostloop(
        g, 17, policy=engine.BeamerHybrid())
    p1 = np.asarray(parents_graph500(fused.state, g.n_vertices))
    p2 = np.asarray(parents_graph500(host_state, g.n_vertices))
    np.testing.assert_array_equal(p1 >= 0, p2 >= 0)
    assert engine.direction_log(fused) == host_log


def test_stats_buffer_matches_hostloop_counters(graphs):
    g = graphs["rmat10"]
    res = engine.traverse(g, 17)
    fused_stats = engine.layer_stats(res)
    _, host_stats, _ = engine.traverse_hostloop(g, 17,
                                                collect_stats=True)
    # the Table 1 counters must agree exactly; the tile accounting
    # legitimately differs (the fused engine streams the full padded
    # E, the hostloop its pow2 buckets)
    assert [s[:4] for s in fused_stats] == [s[:4] for s in host_stats]


def test_hybrid_policy_switches_on_rmat(graphs):
    g = graphs["rmat10"]
    res = engine.traverse(g, 17, policy=engine.BeamerHybrid())
    log = engine.direction_log(res)
    assert log[0] == "topdown" and "bottomup" in log
    check_oracle(g, np.asarray(parents_graph500(res.state,
                                                g.n_vertices)), 17)


def _racy_then_restore(u, v, valid, frontier, visited, parent, n):
    """The Algorithm-3 body as it stood before the jnp path dropped its
    racy bitmap: frontier re-test, racy word scatter, restoration."""
    v_pad = parent.shape[0]
    mask = valid & ~(bm.test_bits(visited, v) | bm.test_bits(frontier, v))
    idx = jnp.where(mask, v, v_pad)
    parent = parent.at[idx].set(u - n, mode="drop")
    out = bm.set_bits_racy(bm.zeros(v_pad), v, mask)
    marked = parent < 0
    repaired = bm.pack_bool(marked)
    return (out | repaired, visited | repaired,
            jnp.where(marked, parent + n, parent))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("bottom_up", [False, True],
                         ids=["topdown", "bottomup"])
@pytest.mark.parametrize("graph_name", ["rmat10", "star"])
def test_simd_body_matches_racy_restore_sequence(graphs, graph_name,
                                                 bottom_up, seed):
    """`expand_candidates(..., "simd")` is bit-identical to the racy
    scatter + restoration sequence on states with frontier ⊆ visited,
    over the xla steps' full-sweep stream in either direction."""
    g = graphs[graph_name]
    n, v_pad = g.n_vertices, g.n_vertices_padded
    owners = engine.edge_owners(g.colstarts, g.n_edges_padded, n)
    u, v = (g.rows, owners) if bottom_up else (owners, g.rows)
    rng = np.random.default_rng(seed)
    seen = rng.random(n) < rng.uniform(0.05, 0.6)
    front = seen & (rng.random(n) < 0.5)
    frontier = bm.set_bits_exact(
        bm.zeros(v_pad), jnp.asarray(np.flatnonzero(front), jnp.int32))
    visited = bm.set_bits_exact(
        csr_mod.init_visited(g),
        jnp.asarray(np.flatnonzero(seen), jnp.int32))
    parent = np.full(v_pad, n, np.int32)
    parent[np.flatnonzero(seen)] = rng.integers(0, n, int(seen.sum()))
    parent = jnp.asarray(parent)
    valid = (g.rows < n) & bm.test_bits(frontier, u)
    got = engine.expand_candidates(u, v, valid, frontier, visited,
                                   parent, n, "simd")
    want = _racy_then_restore(u, v, valid, frontier, visited, parent, n)
    assert int(bm.popcount(got[0])) > 0
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serve_engine_drains_queue(graphs):
    g = graphs["rmat10"]
    eng = GraphEngine(g, batch_slots=4)
    roots = [3, 7, 11, 100, 511, 900]
    for uid, r in enumerate(roots):
        eng.submit(BfsQuery(uid=uid, root=r))
    eng.run_until_done()
    assert len(eng.finished) == len(roots)
    for q in sorted(eng.finished, key=lambda q: q.uid):
        check_oracle(g, q.parent, roots[q.uid])


def test_serve_engine_flags_truncated_queries(graphs):
    """A query that hits the layer budget must be marked partial."""
    g = graphs["path"]
    eng = GraphEngine(g, batch_slots=1, max_layers=8)
    eng.submit(BfsQuery(uid=0, root=0))
    eng.run_until_done()
    q = eng.finished[0]
    assert q.truncated and q.n_layers == 8
    assert (q.parent[:8] >= 0).all()      # prefix reached...
    assert q.parent[50] == -1             # ...deep vertices not yet


def test_serve_engine_reuses_slots(graphs):
    """More queries than slots forces continuous-batching refills."""
    g = graphs["star"]
    eng = GraphEngine(g, batch_slots=2)
    for uid in range(5):
        eng.submit(BfsQuery(uid=uid, root=uid))
    ticks = eng.run_until_done()
    assert len(eng.finished) == 5
    assert ticks >= 3                 # at least ceil(5/2) waves
    for q in eng.finished:
        assert q.parent[q.root] == q.root
