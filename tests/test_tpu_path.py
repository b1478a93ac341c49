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
