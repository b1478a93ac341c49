"""Closed loop of whole searches over the chips of a mesh.

The `searches` loop, run under ``jax.set_mesh`` over a 1-D mesh of
the cell's first ``chips`` devices: ``plan(g)`` then partitions the
graph over them (`repro.core.bfs_distributed.partition_on_mesh`) and
each search runs the per-chip program in one launch.  Traffic
parameters as for `searches`.  The record's notes add what the
program recorded: the partition's gauges (real slots on the fullest
chip and per chip on average, the seconds it took) and the
exchange's wire bytes per search.
"""
from __future__ import annotations

import numpy as np

from bench.drivers import searches

AXIS = "chips"
GAUGES = ("bfs.mesh.slots_max", "bfs.mesh.slots_mean",
          "bfs.mesh.partition_s")
WIRE = "bfs.mesh.exchange_bytes"


def run(ctx):
    import jax

    from repro.core import bfs_distributed
    from repro.obs.metrics import get_registry

    if not hasattr(bfs_distributed, "partition_on_mesh"):
        # without it plan() ignores the mesh and the one-chip program
        # would run replicated on every chip, the whole graph on each
        raise RuntimeError("this program's plan() does not search over "
                           "the mesh set by jax.set_mesh")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:ctx.cell.chips]),
                             (AXIS,))
    registry = get_registry()
    wire0 = registry.counter(WIRE).value
    with jax.set_mesh(mesh):
        record = searches.run(ctx)
    n = len(record.searches)
    record.notes.update(
        {name: registry.gauge(name).value for name in GAUGES},
        mesh_devices=[d.id for d in mesh.devices.flat],
        exchange_bytes_per_search=(registry.counter(WIRE).value - wire0)
        / max(n, 1))
    return record
