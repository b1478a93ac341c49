"""The benchmark's generator copy, the plain reference and the
comparison, against the program at small scale on the CPU."""
import jax
import numpy as np
import pytest

from bench import harness, reference
from bench.tests import small

rmat = harness.generator("rmat")


@pytest.fixture(scope="module")
def graph():
    src, dst = rmat.generate(5, 9)
    return (np.asarray(src), np.asarray(dst),
            reference.host_graph(np.asarray(src), np.asarray(dst), 1 << 9))


@pytest.mark.parametrize("seed, scale", [(0, 8), (5, 9), (2**31 + 7, 8)])
def test_generator_copy_matches_program(seed, scale):
    from repro.core import rmat as program_rmat
    src, dst = rmat.generate(seed, scale)
    ref = program_rmat.generate(jax.random.key(seed), scale)
    np.testing.assert_array_equal(np.asarray(src), np.asarray(ref.src))
    np.testing.assert_array_equal(np.asarray(dst), np.asarray(ref.dst))


def test_large_seeds_differ_above_32_bits():
    a = np.asarray(rmat.generate(3, 6)[0])
    b = np.asarray(rmat.generate(3 + 2**32, 6)[0])
    assert not np.array_equal(a, b)


def test_levels_match_a_queue_bfs(graph):
    _, _, g = graph
    root = int(np.flatnonzero(g.degrees > 0)[0])
    level = {root: 0}
    queue = [root]
    for u in queue:
        for w in g.adj[g.offsets[u]:g.offsets[u + 1]]:
            if int(w) not in level:
                level[int(w)] = level[u] + 1
                queue.append(int(w))
    want = np.full(g.n_vertices, -1)
    for k, d in level.items():
        want[k] = d
    np.testing.assert_array_equal(reference.bfs_levels(g, root), want)


def _program_trees(src, dst, roots):
    import repro.bfs as bfs
    from repro.core import csr
    from repro.core.rmat import EdgeList
    g = csr.from_edges(EdgeList(src, dst, 1 << 9))
    ct = bfs.plan(g, bfs.TraversalSpec(**small.SPEC))
    res = ct.run_batched(np.asarray(roots, np.int32))
    return np.asarray(bfs.parents_graph500(res.state, g.n_vertices))


def test_program_trees_pass(graph):
    src, dst, g = graph
    roots = np.flatnonzero(g.degrees > 0)[:3]
    for root, parent in zip(roots, _program_trees(src, dst, roots)):
        assert reference.wrong_vertices(g, parent, int(root)) == 0


def test_served_trees_pass(graph):
    from repro.core import csr
    from repro.core.rmat import EdgeList
    from repro.serve.graph_engine import BfsQuery, GraphEngine
    import repro.bfs as bfs
    src, dst, g = graph
    eng = GraphEngine(csr.from_edges(EdgeList(src, dst, 1 << 9)),
                      batch_slots=4, spec=bfs.TraversalSpec(**small.SPEC))
    roots = np.flatnonzero(g.degrees > 0)[:6]
    for uid, root in enumerate(roots):
        eng.submit(BfsQuery(uid=uid, root=int(root)))
    eng.run_until_done()
    assert len(eng.finished) == len(roots)
    for q in eng.finished:
        assert reference.wrong_vertices(g, q.parent, q.root) == 0


def test_rejects_one_wrong_parent(graph):
    src, dst, g = graph
    root = int(np.flatnonzero(g.degrees > 0)[0])
    parent = _program_trees(src, dst, [root])[0].copy()
    level = reference.bfs_levels(g, root)
    # a reached vertex given a parent that is not its neighbour
    v = int(np.flatnonzero(level == 2)[0])
    stranger = int(np.setdiff1d(np.flatnonzero(level == 1),
                                g.adj[g.offsets[v]:g.offsets[v + 1]])[0])
    parent[v] = stranger
    assert reference.wrong_vertices(g, parent, root) == 1


def test_rejects_a_wrong_depth(graph):
    src, dst, g = graph
    root = int(np.flatnonzero(g.degrees > 0)[0])
    parent = _program_trees(src, dst, [root])[0].copy()
    level = reference.bfs_levels(g, root)
    # a neighbour on the same level: a real edge, one level too deep
    for v in np.flatnonzero(level == 2):
        nbrs = g.adj[g.offsets[v]:g.offsets[v + 1]]
        same = nbrs[(level[nbrs] == 2) & (nbrs != v)]
        if same.size:
            parent[v] = int(same[0])
            break
    else:
        pytest.fail("no edge inside level 2")
    assert reference.wrong_vertices(g, parent, root) == 1


def test_rejects_reach_and_root_faults(graph):
    src, dst, g = graph
    root = int(np.flatnonzero(g.degrees > 0)[0])
    parent = _program_trees(src, dst, [root])[0]
    reached = int(np.flatnonzero((parent >= 0)
                                 & (np.arange(len(parent)) != root))[0])
    unreached = parent.copy()
    unreached[reached] = -1
    assert reference.wrong_vertices(g, unreached, root) == 1
    moved_root = parent.copy()
    moved_root[root] = reached
    assert reference.wrong_vertices(g, moved_root, root) == 1
    assert reference.wrong_vertices(g, parent[:-1], root) == g.n_vertices
