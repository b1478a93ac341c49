"""The four-chip search cell's pieces on the CPU: its generator, its
driver end to end through the harness on four host devices (in a
subprocess, as the device count is fixed when JAX starts), and its
metric readers on hand-made runs."""
import json
import os
import subprocess
import sys
import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, work
from bench.generators import rmat_blocked

METRICS = harness.BENCH / "metrics"


@pytest.mark.parametrize("scale", [1, 7, 10])
def test_permute_is_a_bijection(scale):
    x = jnp.arange(1 << scale, dtype=jnp.int32)
    y = np.asarray(rmat_blocked.permute(x, jax.random.key(3), scale))
    assert sorted(y.tolist()) == list(range(1 << scale))


def test_blocked_generator_relabels_one_structure(monkeypatch):
    # blocks of 2^10 edges: several blocks and a partial last one
    monkeypatch.setattr(rmat_blocked, "BLOCK", 1 << 10)
    cfg = {"scale": 9, "structure_seed": 9, "edgefactor": 5}
    graphs = [rmat_blocked.for_config(cfg, seed) for seed in (1, 2**33 + 5)]
    degrees = []
    for src, dst, v, (structure_seed, label) in graphs:
        src, dst = np.asarray(src), np.asarray(dst)
        n = int(round(5 * v))
        assert v == 512 and structure_seed == 9 and src.shape == (2 * n,)
        assert sorted(label.tolist()) == list(range(v))
        # symmetrized: the second half is the first half reversed
        np.testing.assert_array_equal(src[n:], dst[:n])
        np.testing.assert_array_equal(dst[n:], src[:n])
        degrees.append(np.bincount(src, minlength=v)[label])
    # the same structure under two labellings
    np.testing.assert_array_equal(degrees[0], degrees[1])
    assert degrees[0].max() > 8 * degrees[0].mean()     # R-MAT's skew


@pytest.mark.parametrize("ranges", [1, 4, 8])
def test_permute_within_keeps_each_range(ranges):
    scale = 9
    x = jnp.arange(1 << scale, dtype=jnp.int32)
    y = np.asarray(rmat_blocked.permute_within(x, jax.random.key(5), scale,
                                               ranges))
    assert sorted(y.tolist()) == list(range(1 << scale))
    size = (1 << scale) // ranges
    np.testing.assert_array_equal(y // size, np.arange(1 << scale) // size)
    assert (y != np.arange(1 << scale)).any()


def test_every_seed_gives_each_chip_the_same_slots():
    # a four-chip configuration: the run's seed relabels inside each
    # chip's quarter of the labels, so the 1-D partition's per-chip
    # slot counts (which set the time of every layer) never move
    cfg = {"scale": 10, "structure_seed": 10, "edgefactor": 16, "chips": 4}
    slots, labels = [], []
    for seed in (1, 2, 2**31 + 17):
        src, _, v, (_, label) = rmat_blocked.for_config(cfg, seed)
        slots.append(np.bincount(np.asarray(src) // (v // 4), minlength=4))
        labels.append(label)
    for other in slots[1:]:
        np.testing.assert_array_equal(slots[0], other)
    assert slots[0].max() > slots[0].min()      # R-MAT's skew shows
    assert (labels[0] != labels[1]).any() and (labels[1] != labels[2]).any()
    with pytest.raises(ValueError, match="power of two"):
        rmat_blocked.for_config(dict(cfg, chips=3), 1)


_RUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import time
    import numpy as np
    from bench import harness
    from bench.tests import small

    cell = harness.Cell(
        "test.mesh4", 4,
        small.config(11, generator="rmat_blocked", spec=None, chips=4),
        {"driver": "mesh_searches", "batch": 1, "roots": 8},
        ["setup_s", "teps"], ["mesh.expand_s_per_search"])
    driver = harness.load_module(harness.BENCH / "drivers"
                                 / "mesh_searches.py")

    def planted(ctx):
        record = driver.run(ctx)
        root, parent = record.trees[0]
        parent = np.array(parent)
        child = next(v for v in np.flatnonzero(parent >= 0) if v != root)
        parent[child] = child           # not a neighbour one level up
        record.trees[0] = (root, parent)
        return record

    out = {}
    for name, drive in (("sound", None), ("planted", planted)):
        result, notes = harness.run_cell(cell, 2**31 + 17, 1.0, False,
                                         time.perf_counter(),
                                         require_tpu=False, drive=drive)
        out[name] = {"result": result, "notes": {
            k: notes[k] for k in ("compiles_window", "searches",
                                  "bfs.mesh.slots_max",
                                  "bfs.mesh.slots_mean",
                                  "exchange_bytes_per_search",
                                  "mesh_devices")}}
    print("RESULT" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def mesh_runs():
    root = str(harness.ROOT)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([root, os.path.join(root, "src")]))
    out = subprocess.run([sys.executable, "-c", _RUN], env=env, cwd=root,
                         capture_output=True, text=True, timeout=600)
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")]
    assert line, out.stderr[-3000:]
    return json.loads(line[0][len("RESULT"):])


def test_mesh_cell_runs_correct_on_four_devices(mesh_runs):
    run = mesh_runs["sound"]
    result, notes = run["result"], run["notes"]
    assert result["correct"] and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert result["checks"]["wrong_vertices"]["value"] == 0
    assert result["metrics"]["teps"]["value"] > 0
    assert notes["compiles_window"] == 0 and notes["searches"] >= 1
    assert notes["mesh_devices"] == [0, 1, 2, 3]
    assert notes["bfs.mesh.slots_max"] >= notes["bfs.mesh.slots_mean"] > 0
    assert notes["exchange_bytes_per_search"] > 0


def test_mesh_cell_refuses_one_wrong_parent(mesh_runs):
    result = mesh_runs["planted"]["result"]
    assert not result["correct"]
    assert result["checks"]["wrong_vertices"]["value"] == 1
    assert result["failed"] == 1


def test_mesh_driver_fails_fast_without_the_mesh_path(monkeypatch):
    from repro.core import bfs_distributed
    monkeypatch.delattr(bfs_distributed, "partition_on_mesh")
    driver = harness.load_module(harness.BENCH / "drivers"
                                 / "mesh_searches.py")
    with pytest.raises(RuntimeError, match="mesh"):
        driver.run(None)


def _run(expand_s=None, busy_s=30.0, window_s=40.0, n=3):
    trace = types.SimpleNamespace(
        scope_s={"bfs.expand": expand_s} if expand_s else {},
        busy_s=busy_s, window_s=window_s)
    return types.SimpleNamespace(
        record=harness.Record(window_s=window_s, attempted=n,
                              searches=[{}] * n),
        trace=trace, n_vertices=1 << 24, n_slots=1 << 29,
        device_kind="TPU v5 lite",
        cell=types.SimpleNamespace(chips=4))


def _read(name, run):
    return harness.load_module(METRICS / f"{name}.py").read(run)


def test_mesh_readers():
    run = _run(expand_s=24.0)
    assert _read("mesh.expand_s_per_search", run) == pytest.approx(8.0)
    assert _read("mesh.exchange_s_per_search", run) == pytest.approx(2.0)
    assert _read("device.idle_share.mesh4", run) == pytest.approx(25.0)
    least_s = work.bytes_per_search(1 << 24, 1 << 29) \
        / (4 * work.peak("TPU v5 lite"))
    assert _read("mesh.expand_roofline_share", run) == pytest.approx(
        100 * least_s / 8.0)
    # no expansion scope in the trace (a program without it): nothing
    for name in ("mesh.expand_s_per_search", "mesh.exchange_s_per_search",
                 "mesh.expand_roofline_share"):
        assert _read(name, _run()) is None
        assert _read(name, types.SimpleNamespace(
            trace=None, record=harness.Record(window_s=1.0,
                                              attempted=0))) is None
