"""Distributed BFS: semantics on a 1-device mesh in-process, true
multi-device semantics in a subprocess with 8 forced host devices
(keeping this process at 1 device, as the dry-run isolation requires).
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import csr as csr_mod
from repro.core import rmat
from repro.core.bfs_distributed import (partition_csr, partition_sizes,
                                        run_bfs_distributed)
from repro.core.bfs_serial import bfs_serial
from repro.core.validate import validate


@pytest.fixture(scope="module")
def g10():
    return csr_mod.from_edges(
        rmat.generate(jax.random.PRNGKey(2), scale=10, edgefactor=16))


def test_partition_covers_all_edges(g10):
    rows_sh, colstarts_sh = partition_csr(g10, 4)
    rows_sh, colstarts_sh = np.asarray(rows_sh), np.asarray(colstarts_sh)
    total = sum(int(colstarts_sh[d, -1]) for d in range(4))
    assert total == g10.n_edges
    # every device's real edges match the global CSR slice
    v_loc = colstarts_sh.shape[1] - 1
    cs = np.asarray(g10.colstarts)
    rows = np.asarray(g10.rows)
    for d in range(4):
        lo, hi = d * v_loc, min((d + 1) * v_loc, g10.n_vertices)
        if lo >= g10.n_vertices:
            continue
        want = rows[cs[lo]:cs[hi]]
        np.testing.assert_array_equal(rows_sh[d, :len(want)], want)


def test_partition_capacity_is_measured_max(g10):
    rows_sh, colstarts_sh = partition_csr(g10, 8)
    colstarts_sh = np.asarray(colstarts_sh)
    real_max = max(int(colstarts_sh[d, -1]) for d in range(8))
    e_loc = rows_sh.shape[1]
    assert e_loc >= real_max and e_loc - real_max < 128
    # padding slots carry the sentinel
    for d in range(8):
        n = int(colstarts_sh[d, -1])
        assert (np.asarray(rows_sh[d, n:]) == g10.n_vertices).all()


def test_partition_sizes_aligned():
    v_loc, e_loc = partition_sizes(1 << 20, 2 * 16 << 20, 256)
    assert v_loc % 128 == 0 and e_loc % 128 == 0
    assert v_loc * 256 >= 1 << 20


def test_distributed_single_device_matches_oracle(g10):
    mesh = jax.make_mesh((1,), ("x",))
    parent, layers = run_bfs_distributed(g10, 11, mesh)
    p = np.asarray(parent)
    p = np.where(p >= g10.n_vertices, -1, p)
    _, ref_depth = bfs_serial(np.asarray(g10.rows),
                              np.asarray(g10.colstarts),
                              g10.n_vertices, 11)
    res = validate(g10, p, 11, reference_depth=ref_depth)
    assert res.ok, res
    assert int(layers) == int(ref_depth.max()) + 1


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import numpy as np
    from repro.core import csr as csr_mod, rmat
    from repro.core.bfs_distributed import run_bfs_distributed
    from repro.core.bfs_serial import bfs_serial
    from repro.core.validate import validate

    assert len(jax.devices()) == 8
    g = csr_mod.from_edges(
        rmat.generate(jax.random.PRNGKey(2), scale=10, edgefactor=16))
    for mesh_shape, names in [((8,), ("x",)), ((2, 4), ("a", "b"))]:
        mesh = jax.make_mesh(mesh_shape, names)
        parent, layers = run_bfs_distributed(g, 11, mesh)
        p = np.asarray(parent)
        p = np.where(p >= g.n_vertices, -1, p)
        _, ref = bfs_serial(np.asarray(g.rows), np.asarray(g.colstarts),
                            g.n_vertices, 11)
        res = validate(g, p, 11, reference_depth=ref)
        assert res.ok, (mesh_shape, res)
    print("MULTIDEV_OK")
""")


def test_distributed_eight_devices_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    # CPU only: a child never takes a chip the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "MULTIDEV_OK" in out.stdout, out.stderr[-3000:]


def test_distributed_deterministic_tree(g10):
    """min-parent merge => identical tree across runs (unlike 1-chip)."""
    mesh = jax.make_mesh((1,), ("x",))
    p1, _ = run_bfs_distributed(g10, 7, mesh)
    p2, _ = run_bfs_distributed(g10, 7, mesh)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
