"""The chip path as the program chooses it, checked on the CPU.

Resolution is steered to the TPU backend inside each test
(``jax.default_backend`` reports "tpu"); nothing here compiles for or
touches a chip.  Also: ``chip_smoke.py`` refuses to run without a TPU,
and the compile cache lives in exactly one place.
"""
from __future__ import annotations

import os
import pathlib
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.bfs as bfs
from repro import compile_cache
from repro.core import bitmap as bm
from repro.core import csr as csr_mod
from repro.core import engine, rmat
from repro.core.bfs_serial import bfs_serial
from repro.core.rmat import EdgeList
from repro.core.validate import validate
from repro.errors import KernelRefusedError
from repro.formats import registry
from repro.kernels import TPU_REFUSALS
from repro.obs import clear_degrade_log, degrade_log

REPO = pathlib.Path(__file__).resolve().parents[1]
FORMATS = ("csr", "sell", "bitmap")
REFUSED = ("fused_gather", "materialized", "megakernel", "persistent")


def _path_graph(n=96):
    a = np.arange(n - 1, dtype=np.int32)
    src = np.concatenate([a, a + 1])
    dst = np.concatenate([a + 1, a])
    return csr_mod.from_edges(EdgeList(jax.numpy.asarray(src),
                                       jax.numpy.asarray(dst), n))


GRAPHS = {
    # the committed affinity table picks megakernel for skewed RMAT
    # and persistent for the thin-layer path — both refused on a TPU
    "rmat9": lambda: csr_mod.from_edges(
        rmat.generate(jax.random.PRNGKey(0), scale=9)),
    "path": _path_graph,
}


@pytest.fixture(scope="module")
def graphs():
    return {name: make() for name, make in GRAPHS.items()}


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("graph", list(GRAPHS))
@pytest.mark.parametrize("fmt_name", FORMATS)
def test_auto_never_resolves_to_a_refused_pipeline(graphs, graph,
                                                   fmt_name, on_tpu):
    fmt = registry.get(fmt_name).from_graph(graphs[graph])
    clear_degrade_log()
    resolved = bfs.TraversalSpec().resolve(fmt)
    assert resolved.pipeline in fmt.tpu_pipelines
    assert resolved.prefetch_depth == 0
    # the legacy loose-knob default follows the same rule
    legacy = engine.make_spec().resolve(fmt)
    assert legacy.pipeline in fmt.tpu_pipelines
    assert degrade_log() == ()


@pytest.mark.parametrize("pipeline", REFUSED)
@pytest.mark.parametrize("fmt_name", ("csr", "sell"))
def test_explicit_refused_pipeline_raises_typed_error(graphs, fmt_name,
                                                      pipeline, on_tpu):
    fmt = registry.get(fmt_name).from_graph(graphs["rmat9"])
    with pytest.raises(KernelRefusedError) as err:
        bfs.plan(fmt, bfs.TraversalSpec(pipeline=pipeline))
    refusals = TPU_REFUSALS[pipeline]
    assert refusals and all(f"{k}: {r}" in str(err.value)
                            for k, r in refusals.items())


def test_semiring_on_tpu_raises_typed_error(graphs, on_tpu):
    with pytest.raises(KernelRefusedError, match="gather_relax"):
        bfs.plan(graphs["rmat9"], bfs.TraversalSpec(algorithm="sssp"))


def test_refused_pipelines_still_plan_off_tpu(graphs):
    """Off a TPU (the interpreter) every pipeline stays available."""
    for pipeline in REFUSED:
        ct = bfs.plan(graphs["rmat9"],
                      bfs.TraversalSpec(pipeline=pipeline))
        assert ct.resolved.pipeline == pipeline


@pytest.mark.parametrize("fmt_name", FORMATS)
def test_xla_pipeline_trees_validate(graphs, fmt_name):
    g = graphs["rmat9"]
    fmt = registry.get(fmt_name).from_graph(g)
    roots = [1, 2, 3, 5]
    ct = bfs.plan(fmt, bfs.TraversalSpec(pipeline="xla",
                                         policy="beamer"))
    parents = bfs.parents_graph500(ct.run_batched(roots).state,
                                   g.n_vertices)
    for i, root in enumerate(roots):
        ref = bfs_serial(np.asarray(g.rows), np.asarray(g.colstarts),
                         g.n_vertices, root)[1]
        assert validate(g, parents[i], root, reference_depth=ref).ok


def test_xla_pipeline_rejects_prefetch(graphs):
    with pytest.raises(ValueError, match="pipeline='xla'"):
        bfs.plan(graphs["rmat9"], bfs.TraversalSpec(pipeline="xla",
                                                    prefetch_depth=1))


@pytest.mark.parametrize("mode", [engine.MODE_SIMD, engine.MODE_BOTTOMUP],
                         ids=["topdown", "bottomup"])
def test_xla_step_sweeps_two_gathers_one_scatter(graphs, mode):
    """Per slot and layer an xla step reads two words and writes at
    most one parent: a pass brought back over the stream fails here."""
    g = graphs["rmat9"]
    e, w = g.n_edges_padded, g.n_vertices_padded // bm.BITS_PER_WORD
    step = engine.make_xla_steps(
        engine.edge_owners(g.colstarts, e, g.n_vertices), g.rows,
        g.n_vertices, "simd", 1)[mode]
    bits = jax.ShapeDtypeStruct((2, w), jnp.uint32)
    text = jax.jit(step).lower(
        bits, bits, jax.ShapeDtypeStruct((2, g.n_vertices_padded),
                                         jnp.int32)).as_text()
    gathers = re.findall(r'"stablehlo\.gather"\(.*: \(tensor<\w+>, '
                         r'tensor<(\d+)x1xi32>\)', text)
    assert gathers == [str(e)] * 2
    assert text.count('"stablehlo.scatter"(') == 1


@pytest.mark.parametrize("fmt_name", ("csr", "sell"))
def test_xla_beamer_layers_keep_frontier_inside_visited(graphs, fmt_name):
    """The invariant the xla body relies on, layer by layer: the
    planned Beamer search's own directions replayed through its steps
    on the host keep ``frontier & ~visited == 0`` and end on the
    whole-search program's state."""
    g = graphs["rmat9"]
    ct = bfs.plan(registry.get(fmt_name).from_graph(g),
                  bfs.TraversalSpec(pipeline="xla", policy="beamer"))
    roots = jnp.asarray([1, 2, 3, 5], jnp.int32)
    res = ct.run_batched(roots)
    log = engine.direction_log(res)
    assert {"topdown", "bottomup"} <= set(log)
    steps = ct.fmt.make_steps(ct.resolved)
    state = engine._init_batched(roots, g.n_vertices,
                                 g.n_vertices_padded)
    for name in log:
        mode = (engine.MODE_BOTTOMUP if name == "bottomup"
                else engine.MODE_SIMD)
        state = steps[mode](*state)[:3]
        assert not np.any(np.asarray(state[0] & ~state[1]))
    for got, want in zip(state, res.state[:3]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _from_pairs(pairs, n):
    """Symmetric CSR of undirected ``pairs`` over ``n`` vertices."""
    a, b = np.asarray(pairs, np.int32).T
    return csr_mod.from_edges(EdgeList(jnp.asarray(np.r_[a, b]),
                                       jnp.asarray(np.r_[b, a]), n))


def _star(leaves, extra_path=0):
    """Hub 0 and ``leaves`` leaves, beside a path of ``extra_path``
    more vertices that no root of the star reaches."""
    pairs = [(0, i) for i in range(1, leaves + 1)]
    first = leaves + 1
    pairs += [(first + i, first + i + 1) for i in range(extra_path - 1)]
    return _from_pairs(pairs, first + extra_path)


def _disconnected():
    """R-MAT 9 on the even ids, a 64-cycle on the first odd ids, and
    every other odd id isolated: zero-degree vertices sit among the
    unvisited set of every bottom-up layer, and one is a root."""
    g = rmat.generate(jax.random.PRNGKey(1), scale=9)
    ring = 2 * np.arange(64) + 1
    src = np.r_[2 * np.asarray(g.src), ring, np.roll(ring, 1)]
    dst = np.r_[2 * np.asarray(g.dst), np.roll(ring, 1), ring]
    return csr_mod.from_edges(EdgeList(jnp.asarray(src, jnp.int32),
                                       jnp.asarray(dst, jnp.int32),
                                       2 * g.n_vertices))


def _rmat(scale):
    return csr_mod.from_edges(rmat.generate(jax.random.PRNGKey(0),
                                            scale=scale))


#: name -> (graph, roots, spec fields); ``csr_stream_sizes`` of each is
#: in the comment
CSR_SEARCHES = {
    "rmat9": (lambda: _rmat(9), [1, 2, 3, 5], {}),
    "rmat10": (lambda: _rmat(10), [1, 2, 3, 5], {}),
    "rmat11": (lambda: _rmat(11), [1, 2, 3, 5], {}),
    # 4096, 1024, 256: the hub's bottom-up layer fills the top bucket
    "star": (lambda: _star(2047), [0, 5], {"tile": 128}),
    # 2048, 512, 128: every layer holds at most four slots
    "path": (lambda: _path_graph(1024), [0, 511],
             {"tile": 128, "max_layers": 1100}),
    "disconnected": (_disconnected, [0, 301, 7], {}),
    # 4096, 1024, 256: the hub's top-down layer holds exactly 256
    # slots (the unreached path keeps Beamer top-down)
    "exact_bucket": (lambda: _star(256, extra_path=1793), [0],
                     {"tile": 128}),
}


@pytest.fixture(scope="module")
def csr_searches():
    return {name: (make(), roots, kw)
            for name, (make, roots, kw) in CSR_SEARCHES.items()}


def _planned_search(csr_searches, name):
    g, roots, kw = csr_searches[name]
    ct = bfs.plan(g, bfs.TraversalSpec(pipeline="xla", policy="beamer",
                                       **kw))
    roots = jnp.asarray(roots, jnp.int32)
    return g, roots, ct, ct.run_batched(roots)


@pytest.mark.parametrize("name", list(CSR_SEARCHES))
def test_csr_xla_step_matches_the_full_sweep(csr_searches, name):
    """The CSR step sweeps the active side's adjacency only.  Replayed
    layer by layer, the full sweep over every slot (`make_xla_steps`
    over `edge_owners`) gives the whole-search program's state bit for
    bit, and every tree has `bfs_serial`'s reach set and depths."""
    g, roots, ct, res = _planned_search(csr_searches, name)
    n = g.n_vertices
    full = engine.make_xla_steps(
        engine.edge_owners(g.colstarts, g.n_edges_padded, n), g.rows, n,
        "simd", 1)
    full = {m: jax.jit(step) for m, step in full.items()}
    state = engine._init_batched(roots, n, g.n_vertices_padded)
    for direction in engine.direction_log(res):
        mode = (engine.MODE_BOTTOMUP if direction == "bottomup"
                else engine.MODE_SIMD)
        state = full[mode](*state)[:3]
    for got, want in zip(res.state[:3], state):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    parents = bfs.parents_graph500(res.state, n)
    for i, root in enumerate(np.asarray(roots).tolist()):
        ref = bfs_serial(np.asarray(g.rows), np.asarray(g.colstarts), n,
                         root)[1]
        assert validate(g, parents[i], root, reference_depth=ref).ok


@pytest.mark.parametrize("name", ["path", "star", "exact_bucket"])
def test_csr_xla_stats_count_the_swept_bucket(csr_searches, name):
    """`LayerStats.active_tiles` is the tile count of the bucket each
    root swept: the floor bucket on every layer of a path, the top one
    on a hub's first bottom-up layer, and a bucket exactly as large as
    the hub's 256 slots."""
    g, roots, ct, res = _planned_search(csr_searches, name)
    sizes = engine.csr_stream_sizes(g.n_edges_padded, g.n_vertices)
    tiles = [-(-s // ct.resolved.tile) for s in sizes]
    stats = engine.layer_stats(res)
    log = engine.direction_log(res)
    n_roots = len(roots)
    if name == "path":
        assert len(sizes) == 3 and len(stats) > 1000
        assert {s.active_tiles for s in stats} == {n_roots * tiles[-1]}
    elif name == "star":
        assert stats[log.index("bottomup")].active_tiles \
            == n_roots * tiles[0]
    else:
        assert (log[0], stats[0].edges_examined) == ("topdown", sizes[-1])
        assert stats[0].active_tiles == n_roots * tiles[-1]
        assert len(set(tiles)) == len(tiles)


def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_fails_without_a_tpu():
    out = _run_smoke(REPO, REPO / "chip_smoke.py")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    out = _run_smoke(tmp_path, tmp_path / "chip_smoke.py")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.fixture
def cache_config():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_uses_the_env_dir(monkeypatch, tmp_path,
                                        cache_config):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
