"""ISSUE 4: packed-word engine — parity, compaction kernel oracle,
and bitmap round-trip properties.

Covers the acceptance matrix:

* **parity** — packed (native) vs unpacked (legacy dense-mask)
  traversal produces bit-identical parents/visited for every format x
  direction policy, both pipelines, batched multi-root, and the
  distributed program at shard counts 1 and 2 (2 via a forced
  host-device subprocess);
* **compaction kernel** — `kernels.compact.frontier_compact[_batched]`
  against a numpy popcount/nonzero oracle, including truncation,
  empty/full bitmaps and non-tile-multiple word counts;
* **round-trip properties** — packed words survive
  pack_bool/unpack_bool/compact/frontier_compact round trips for
  arbitrary bit sets (hypothesis, with the deterministic fallback
  sampler);
* **double-buffered DMA** — prefetch_depth > 0 kernels equal the
  BlockSpec-pipelined kernels exactly;
* **distributed packed merge** — `merge="packed"` returns the same
  deterministic min-parent tree as the per-layer ``pmin`` baseline.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import bitmap as bm
from repro.core import csr as csr_mod
from repro.core import engine, rmat
from repro.core.rmat import EdgeList
from repro.formats.bitmap_format import BitmapCompressedFormat
from repro.formats.csr_format import CsrFormat
from repro.formats.sell import SellFormat
from repro.kernels import compact as ck

POLICIES = {
    "topdown": engine.TopDown(),
    "simd_forced": engine.ThresholdSimd(0),
    "paper_layers": engine.PaperLiteralLayers((1, 2)),
    "hybrid": engine.BeamerHybrid(),
}
FORMATS = {
    "csr": CsrFormat,
    "sell": SellFormat,
    "bitmap": BitmapCompressedFormat,
}


def _csr_from_pairs(pairs, n):
    src = jnp.asarray([a for a, b in pairs] + [b for a, b in pairs],
                      jnp.int32)
    dst = jnp.asarray([b for a, b in pairs] + [a for a, b in pairs],
                      jnp.int32)
    return csr_mod.from_edges(EdgeList(src, dst, n))


@pytest.fixture(scope="module")
def g9():
    return csr_mod.from_edges(
        rmat.generate(jax.random.PRNGKey(3), scale=9, edgefactor=8))


@pytest.fixture(scope="module")
def built(g9):
    return {name: cls.from_csr(g9) for name, cls in FORMATS.items()}


def _state_tuple(res):
    return (np.asarray(res.state.parent), np.asarray(res.state.visited),
            np.asarray(res.state.frontier))


# ---------------------------------------------------------------------------
# Packed vs unpacked parity: formats x policies x pipelines x batched
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pol_name", list(POLICIES))
@pytest.mark.parametrize("fmt_name", list(FORMATS))
def test_packed_parity_formats_policies(built, fmt_name, pol_name):
    fmt = built[fmt_name]
    kw = dict(policy=POLICIES[pol_name])
    a = engine.traverse(fmt, 17, packed=True, **kw)
    b = engine.traverse(fmt, 17, packed=False, **kw)
    for x, y in zip(_state_tuple(a), _state_tuple(b)):
        np.testing.assert_array_equal(x, y)
    # workload stats are representation-independent; the launch-count
    # column is NOT (the packed arm's compaction kernel is one extra
    # Pallas call per layer — an honest cost difference, not a parity
    # break), so compare everything except _ST_LAUNCH
    sa, sb = np.asarray(a.stats), np.asarray(b.stats)
    keep = [i for i in range(engine._N_ST) if i != engine._ST_LAUNCH]
    np.testing.assert_array_equal(sa[:, keep], sb[:, keep])


@pytest.mark.parametrize("pipeline", engine.PIPELINES)
def test_packed_parity_pipelines(g9, pipeline):
    pol = engine.ThresholdSimd(0)
    a = engine.traverse(g9, 17, policy=pol, pipeline=pipeline,
                        packed=True)
    b = engine.traverse(g9, 17, policy=pol, pipeline=pipeline,
                        packed=False)
    for x, y in zip(_state_tuple(a), _state_tuple(b)):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("fmt_name", list(FORMATS))
def test_packed_parity_batched_multiroot(built, fmt_name):
    fmt = built[fmt_name]
    roots = [3, 7, 17, 100]
    a = engine.traverse(fmt, roots, policy=engine.ThresholdSimd(0),
                        packed=True)
    b = engine.traverse(fmt, roots, policy=engine.ThresholdSimd(0),
                        packed=False)
    np.testing.assert_array_equal(np.asarray(a.state.parent),
                                  np.asarray(b.state.parent))
    np.testing.assert_array_equal(np.asarray(a.depths),
                                  np.asarray(b.depths))


def test_packed_parity_hostpath_edge_graphs():
    """Star (hub frontier) and path (1-vertex layers) corner shapes."""
    star = _csr_from_pairs([(0, i) for i in range(1, 128)], 128)
    path = _csr_from_pairs([(i, i + 1) for i in range(95)], 96)
    for g, root in ((star, 0), (path, 0)):
        a = engine.traverse(g, root, policy=engine.ThresholdSimd(0),
                            packed=True, max_layers=128)
        b = engine.traverse(g, root, policy=engine.ThresholdSimd(0),
                            packed=False, max_layers=128)
        np.testing.assert_array_equal(np.asarray(a.state.parent),
                                      np.asarray(b.state.parent))


def test_prefetch_depth_matches_blockspec_pipeline(built):
    """The manual double-buffered DMA input pipeline is a pure
    performance transform: results equal the BlockSpec kernels."""
    for fmt_name in ("csr", "sell"):
        fmt = built[fmt_name]
        base = engine.traverse(fmt, 17, policy=engine.ThresholdSimd(0))
        for depth in (1, 3):
            res = engine.traverse(fmt, 17,
                                  policy=engine.ThresholdSimd(0),
                                  prefetch_depth=depth)
            np.testing.assert_array_equal(np.asarray(res.state.parent),
                                          np.asarray(base.state.parent))


def test_serve_engine_packed_knobs(g9):
    from repro.serve.graph_engine import BfsQuery, GraphEngine
    results = {}
    for packed in (True, False):
        eng = GraphEngine(g9, batch_slots=2, graph_format="csr",
                          packed=packed, prefetch_depth=1 if packed
                          else 0)
        for uid, r in enumerate([3, 7, 17]):
            eng.submit(BfsQuery(uid=uid, root=r))
        eng.run_until_done()
        results[packed] = {q.uid: q.parent for q in eng.finished}
    for uid in results[True]:
        np.testing.assert_array_equal(results[True][uid],
                                      results[False][uid])


# ---------------------------------------------------------------------------
# Distributed: packed merge + shard count 1/2 parity
# ---------------------------------------------------------------------------

def test_distributed_packed_merge_single_shard(g9):
    from repro.core.bfs_distributed import run_bfs_distributed
    mesh = jax.make_mesh((1,), ("x",))
    p_packed, l1 = run_bfs_distributed(g9, 11, mesh, merge="packed")
    p_base, l2 = run_bfs_distributed(g9, 11, mesh, merge="allreduce")
    np.testing.assert_array_equal(np.asarray(p_packed),
                                  np.asarray(p_base))
    assert int(l1) == int(l2)


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    import numpy as np
    from repro.core import csr as csr_mod, rmat
    from repro.core.bfs_distributed import run_bfs_distributed

    assert len(jax.devices()) == 2
    g = csr_mod.from_edges(
        rmat.generate(jax.random.PRNGKey(3), scale=9, edgefactor=8))
    mesh = jax.make_mesh((2,), ("x",))
    p_packed, lp = run_bfs_distributed(g, 11, mesh, merge="packed")
    p_base, lb = run_bfs_distributed(g, 11, mesh, merge="allreduce")
    np.testing.assert_array_equal(np.asarray(p_packed),
                                  np.asarray(p_base))
    assert int(lp) == int(lb)
    print("PACKED2_OK")
""")


def test_distributed_packed_merge_two_shards_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    # CPU only: a child never takes a chip the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "PACKED2_OK" in out.stdout, out.stderr[-3000:]


# ---------------------------------------------------------------------------
# Compaction kernel vs numpy oracle
# ---------------------------------------------------------------------------

def _np_compact(words, size, fill):
    dense = np.unpackbits(
        np.asarray(words, np.uint32).view(np.uint8), bitorder="little")
    ids = np.nonzero(dense)[0]
    out = np.full((size,), fill, np.int32)
    take = min(len(ids), size)
    out[:take] = ids[:take]
    return out, len(ids)


@pytest.mark.parametrize("n_words,size", [(4, 128), (36, 1152),
                                          (40, 64), (257, 8224)])
def test_compact_kernel_vs_numpy(n_words, size):
    rng = np.random.default_rng(n_words)
    words = jnp.asarray(rng.integers(0, 2**32, size=n_words,
                                     dtype=np.uint32))
    q, n = ck.frontier_compact(words, size=size, fill=n_words * 32)
    ref_q, ref_n = _np_compact(words, size, n_words * 32)
    np.testing.assert_array_equal(np.asarray(q), ref_q)
    assert int(n) == ref_n


def test_compact_kernel_batched_vs_numpy():
    rng = np.random.default_rng(0)
    words = jnp.asarray(rng.integers(0, 2**32, size=(5, 36),
                                     dtype=np.uint32))
    q, n = ck.frontier_compact_batched(words, size=1152, fill=1152)
    for b in range(5):
        ref_q, ref_n = _np_compact(words[b], 1152, 1152)
        np.testing.assert_array_equal(np.asarray(q[b]), ref_q)
        assert int(n[b]) == ref_n


def test_compact_kernel_empty_and_full():
    z = jnp.zeros((8,), jnp.uint32)
    q, n = ck.frontier_compact(z, size=16, fill=256)
    assert int(n) == 0 and (np.asarray(q) == 256).all()
    f = jnp.full((8,), 0xFFFFFFFF, jnp.uint32)
    q, n = ck.frontier_compact(f, size=256, fill=256)
    np.testing.assert_array_equal(np.asarray(q), np.arange(256))
    assert int(n) == 256


def test_compact_kernel_truncates_like_bitmap_compact():
    rng = np.random.default_rng(7)
    words = jnp.asarray(rng.integers(0, 2**32, size=16,
                                     dtype=np.uint32))
    q, _ = ck.frontier_compact(words, size=10, fill=512)
    ref = bm.compact(words, 10, 512)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(ref))


# ---------------------------------------------------------------------------
# Round-trip properties (packed words <-> bits <-> queues)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=511), min_size=0,
                max_size=80))
def test_packed_roundtrip_property(vertices):
    """set_bits -> unpack -> pack -> compact -> kernel compact all
    agree for arbitrary bit sets (the core/bitmap.py helpers the
    packed engine is built from)."""
    v_pad = 512
    ids = jnp.asarray(sorted(set(vertices)), jnp.int32)
    words = bm.set_bits_exact(bm.zeros(v_pad), ids)
    # word <-> dense round trip
    np.testing.assert_array_equal(
        np.asarray(bm.pack_bool(bm.unpack_bool(words))),
        np.asarray(words))
    # popcount == cardinality
    assert int(bm.popcount(words)) == len(set(vertices))
    # jnp compact == kernel compact == the sorted id list
    lst = np.asarray(bm.compact(words, v_pad, v_pad))
    q, n = ck.frontier_compact(words, size=v_pad, fill=v_pad)
    np.testing.assert_array_equal(np.asarray(q), lst)
    assert int(n) == len(set(vertices))
    np.testing.assert_array_equal(
        lst[:len(set(vertices))], np.asarray(ids, np.int64))


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=255), min_size=1,
                max_size=40))
def test_masked_degree_sum_property(vertices):
    """The packed Table-1 edge counter equals the dense reference."""
    v = 256
    rng = np.random.default_rng(len(vertices))
    deg = jnp.asarray(rng.integers(0, 50, size=v), jnp.int32)
    ids = jnp.asarray(sorted(set(vertices)), jnp.int32)
    words = bm.set_bits_exact(bm.zeros(v), ids)
    deg_mat = bm.degree_matrix(deg, v)
    packed_sum = int(bm.masked_degree_sum(words, deg_mat))
    dense = np.asarray(bm.unpack_bool(words))[:v]
    assert packed_sum == int(np.asarray(deg)[dense].sum())


# ---------------------------------------------------------------------------
# Planning parity: packed planner == dense planner
# ---------------------------------------------------------------------------

def test_edge_stream_packed_parity(g9):
    """The single-root materialized stream is bit-identical whether
    the frontier list comes from the compaction kernel or the dense
    unpack/nonzero pass."""
    rng = np.random.default_rng(3)
    ids = jnp.asarray(
        np.unique(rng.integers(0, g9.n_vertices, size=50)), jnp.int32)
    words = bm.set_bits_exact(bm.zeros(g9.n_vertices_padded), ids)
    a = engine.edge_stream(g9.colstarts, g9.rows, words,
                           g9.n_vertices_padded, g9.n_vertices,
                           g9.n_edges_padded, packed=True)
    b = engine.edge_stream(g9.colstarts, g9.rows, words,
                           g9.n_vertices_padded, g9.n_vertices,
                           g9.n_edges_padded, packed=False)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_plan_active_tiles_packed_matches_dense(g9):
    fmt = CsrFormat.from_csr(g9)
    tile = fmt.resolve_tile(None)
    rows_t = engine._pad_rows_to_tile(g9.rows, g9.n_vertices, tile)
    n_blocks = int(rows_t.shape[0]) // tile
    rng = np.random.default_rng(1)
    ids = jnp.asarray(
        np.unique(rng.integers(0, g9.n_vertices, size=37)), jnp.int32)
    words = bm.set_bits_exact(bm.zeros(g9.n_vertices_padded), ids)
    wl_p, na_p = engine.plan_active_tiles(
        g9.colstarts, words, g9.n_vertices, tile, n_blocks, packed=True)
    wl_d, na_d = engine.plan_active_tiles(
        g9.colstarts, words, g9.n_vertices, tile, n_blocks,
        packed=False)
    assert int(na_p) == int(na_d)
    np.testing.assert_array_equal(np.asarray(wl_p), np.asarray(wl_d))


def test_compact_fits_budget_fallback():
    """Oversized batch x V_pad working sets must route the packed
    planning arms to the dense fallback instead of failing the
    compaction kernel's VMEM budget (large graphs keep traversing
    exactly as they did before the packed default)."""
    from repro.kernels import ops
    assert ops.compact_fits(1, 1152)
    assert ops.compact_fits(8, 1 << 14)
    assert not ops.compact_fits(8, 1 << 22)   # 128 MiB of queues
    assert not ops.compact_fits(1, 1 << 25)


def test_tile_env_override(monkeypatch):
    monkeypatch.setenv(engine._TILE_ENV, "2048")
    assert engine.default_tile_csr() == 2048
    monkeypatch.delenv(engine._TILE_ENV)
    # without the env the committed BENCH table (or the 1024 fallback)
    # decides; either way the resolved tile respects the floor
    t = engine._resolve_tile_csr(None, 1 << 16)
    assert t >= 128
