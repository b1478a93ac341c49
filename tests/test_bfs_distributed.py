"""Distributed BFS: semantics on a 1-device mesh in-process, true
multi-device semantics in a subprocess with 8 forced host devices
(keeping this process at 1 device, as the dry-run isolation requires).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import csr as csr_mod
from repro.core import rmat
from repro.core.bfs_distributed import (exchange_bytes, partition_csr,
                                        partition_sizes,
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


# ---------------------------------------------------------------------------
# plan(g, mesh) on four devices: lower().compile(), run_batched, the
# plain reference and the validator, every merge
# ---------------------------------------------------------------------------

_PLAN_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax
    import numpy as np
    import repro.bfs as bfs
    from bench import reference
    from repro.core import csr as csr_mod, rmat
    from repro.core.bfs_serial import bfs_serial
    from repro.core.validate import validate
    from repro.obs.metrics import get_registry

    mesh = jax.make_mesh((4,), ("x",))
    out = {}
    for scale in (10, 11):
        edges = rmat.generate(jax.random.PRNGKey(scale), scale=scale,
                              edgefactor=16)
        g = csr_mod.from_edges(edges)
        hg = reference.host_graph(np.asarray(edges.src),
                                  np.asarray(edges.dst), g.n_vertices)
        roots = np.flatnonzero(hg.degrees > 0)[[0, 7, 99]].astype(np.int32)
        for merge in ("allreduce", "owner", "packed"):
            ct = bfs.plan(g, bfs.TraversalSpec(merge=merge), mesh=mesh)
            ct.lower(roots).compile()
            res = ct.run_batched(roots)
            parents = np.asarray(bfs.parents_graph500(res.state,
                                                      g.n_vertices))
            for root, p, layers in zip(roots.tolist(), parents,
                                       np.asarray(res.depths).tolist()):
                level = reference.bfs_levels(hg, root)
                _, ref_depth = bfs_serial(np.asarray(g.rows),
                                          np.asarray(g.colstarts),
                                          g.n_vertices, root)
                out.setdefault(merge, []).append({
                    "scale": scale, "root": root,
                    "wrong": reference.wrong_vertices(hg, p, root, level),
                    "valid": bool(validate(g, p, root,
                                           reference_depth=ref_depth).ok),
                    "layers": layers, "depth": int(level.max())})
    out["metrics"] = {name: m.value for name, m in get_registry()
                      if name.startswith("bfs.mesh.")}
    out["slots"] = [int(s) for s in ct._partition.slots]
    out["n_edges"] = g.n_edges
    print("RESULT" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def four_device_plans():
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([root,
                                           os.path.join(root, "src")]))
    out = subprocess.run([sys.executable, "-c", _PLAN_SUBPROC], env=env,
                         capture_output=True, text=True, timeout=900)
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")]
    assert line, out.stderr[-3000:]
    return json.loads(line[0][len("RESULT"):])


@pytest.mark.parametrize("merge", ["allreduce", "owner", "packed"])
def test_plan_on_four_devices_matches_reference(four_device_plans, merge):
    trees = four_device_plans[merge]
    assert len(trees) == 6          # three roots at each of scales 10, 11
    for t in trees:
        assert t["wrong"] == 0, t
        assert t["valid"], t
        assert t["layers"] == t["depth"] + 1, t


def test_four_device_search_records_mesh_metrics(four_device_plans):
    m = four_device_plans["metrics"]
    slots = four_device_plans["slots"]
    assert sum(slots) == four_device_plans["n_edges"]
    assert m["bfs.mesh.slots_max"] == max(slots)
    assert m["bfs.mesh.slots_mean"] == pytest.approx(sum(slots) / 4)
    assert m["bfs.mesh.partition_s"] > 0
    # every merge put bytes on the wire, counted from its layers
    assert m["bfs.mesh.exchange_bytes"] > 0


def test_exchange_bytes_per_merge():
    v, d = 1 << 20, 4
    # packed: a V/8-byte bitmap all-gathered per layer, one 4V pmin
    assert exchange_bytes("packed", v, d, 7) == round(
        d * (7 * v // 8 * 3 + 2 * 4 * v * 3 / 4))
    # allreduce: a 4V pmin per layer
    assert exchange_bytes("allreduce", v, d, 7) == round(
        d * 7 * 2 * 4 * v * 3 / 4)
    # owner: a 4V all-to-all and a V/(8 D) bitmap all-gather per layer
    assert exchange_bytes("owner", v, d, 7) == round(
        d * 7 * (4 * v * 3 / 4 + v // 8 // d * 3))
    assert exchange_bytes("packed", v, 1, 7) == 0
