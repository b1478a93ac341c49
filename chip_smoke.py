"""Run the BFS main path once on a TPU and check every answer.

    python chip_smoke.py              # one chip, Graph500 rmat-20
    python chip_smoke.py --chips 4    # the mesh path over four chips

One chip: generate the Graph500 RMAT graph from a seed, plan it with
the default `TraversalSpec`, run two batches of 8 roots through
`CompiledTraversal.run_batched`, then serve 16 queries through a
`GraphEngine`.  Every BFS tree passes the Graph500 validator, one
root's depths match the serial oracle, and the run fails on any serve
retry, poisoned slot or degrade event.  ``--chips 4`` runs only the
mesh-bound plan (`plan(g, mesh=...)`) on a few roots, each tree
validated and its depths compared with the one-chip plan on device 0,
and each chip holding its own share of the edges.

One process drives every chip and nothing is caught, except a
compile refusal at rmat-20: rmat-19 then runs and the reason is
printed.  The script exits non-zero, with no result line, where JAX
finds no TPU.  The last line of standard output is the JSON result;
times printed before it are information, not measurements.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))


SCALE = 20      # Graph500 rmat-20 (configs/bfs_graph500.py)
SEED = 0


def _log(msg: str) -> None:
    print(msg, flush=True)


def _graph(scale: int):
    import jax

    from repro.core import csr, rmat
    t0 = time.perf_counter()
    g = csr.from_edges(rmat.generate(jax.random.key(SEED), scale))
    g.rows.block_until_ready()
    _log(f"graph: rmat-{scale} (edgefactor 16) |V|={g.n_vertices} "
         f"|E|={g.n_edges} directed, built in "
         f"{time.perf_counter() - t0:.2f} s")
    return g


def _roots(g, n: int):
    import numpy as np
    deg = np.asarray(g.degrees())
    rng = np.random.default_rng(SEED)
    return rng.choice(np.flatnonzero(deg > 0), n,
                      replace=False).astype(np.int32)


def _check_tree(g, parent, root: int, reference_depth=None):
    from repro.core.validate import validate
    v = validate(g, parent, int(root), reference_depth=reference_depth)
    if not v.ok:
        raise AssertionError(
            f"root {root}: BFS tree fails validation "
            f"{v._replace(depth=None)}")
    return v.depth


def _pallas_kernels(hlo_text: str) -> list[str]:
    """Names of the Mosaic kernels compiled into a lowered program."""
    import re
    return sorted({m.group(1) for m in re.finditer(
        r'kernel_name\s*=\s*"([^"]+)"', hlo_text)})


def _compile(ct, roots):
    """Compile the whole-search program for this batch shape; returns
    (seconds, Pallas kernel names).  Fails if Pallas kernels would run
    in the interpreter."""
    import jax.numpy as jnp

    from repro.kernels import interpret_mode
    if interpret_mode():
        raise AssertionError("Pallas kernels would run in the "
                             "interpreter on the chip path")
    t0 = time.perf_counter()
    lowered = ct.lower(jnp.asarray(roots))
    kernels = _pallas_kernels(lowered.as_text())
    lowered.compile()
    return time.perf_counter() - t0, kernels


def one_chip(scale: int = SCALE) -> None:
    import jax
    import numpy as np

    import repro.bfs as bfs
    from repro.core.bfs_serial import bfs_serial
    from repro.obs import degrade_log, get_registry
    from repro.serve.graph_engine import BfsQuery, GraphEngine

    g = _graph(scale)
    roots = _roots(g, 16)
    ct = bfs.plan(g)
    try:
        compile_s, kernels = _compile(ct, roots[:8])
    except jax.errors.JaxRuntimeError as e:
        if scale <= 19:
            raise
        _log(f"rmat-{scale} refused by the compiler, running rmat-19: "
             f"{str(e).splitlines()[0]}")
        return one_chip(19)
    spec = ct.resolved
    _log(f"resolved spec: {json.dumps(spec.to_dict(), default=str)}")
    _log(f"expansion path: pipeline={spec.pipeline!r} on "
         f"{ct.fmt.name}; Pallas kernels compiled for the chip: "
         f"{', '.join(kernels) or 'none'}")
    _log(f"compile: {compile_s:.2f} s (whole-search program, batch 8)")

    t0 = time.perf_counter()
    ref_depth = bfs_serial(np.asarray(g.rows), np.asarray(g.colstarts),
                           g.n_vertices, int(roots[0]))[1]
    _log(f"serial oracle for root {roots[0]}: "
         f"{time.perf_counter() - t0:.2f} s")
    for b in range(2):
        batch = roots[8 * b:8 * b + 8]
        t0 = time.perf_counter()
        res = ct.run_batched(batch)
        res.state.parent.block_until_ready()
        wall = time.perf_counter() - t0
        parents = bfs.parents_graph500(res.state, g.n_vertices)
        for i, root in enumerate(batch):
            _check_tree(g, parents[i], root,
                        ref_depth if (b, i) == (0, 0) else None)
        _log(f"plan batch {b}: roots {batch.tolist()} valid; layers "
             f"{np.asarray(res.depths).tolist()}; directions "
             f"{ct.direction_log(res)}; {wall:.2f} s wall")
    if ct.traces != 1:
        raise AssertionError(f"plan traced {ct.traces} times, expected 1")

    t0 = time.perf_counter()
    eng = GraphEngine(g, batch_slots=8)
    _log(f"serve: format {eng.fmt.name!r}, pipeline "
         f"{eng.resolved.pipeline!r}, built in "
         f"{time.perf_counter() - t0:.2f} s")
    for uid, root in enumerate(roots):
        eng.submit(BfsQuery(uid=uid, root=int(root)))
    t0 = time.perf_counter()
    ticks = eng.run_until_done()
    wall = time.perf_counter() - t0
    if len(eng.finished) != len(roots):
        raise AssertionError(f"served {len(eng.finished)} of "
                             f"{len(roots)} queries")
    for q in eng.finished:
        if q.truncated or q.error is not None:
            raise AssertionError(f"query {q.uid} truncated: {q.error!r}")
        _check_tree(g, q.parent, q.root)
    reg = get_registry()
    faults = {name: reg.counter(name).value
              for name in ("serve.retries", "serve.poisoned")}
    if any(faults.values()) or degrade_log():
        raise AssertionError(f"serve faults {faults}, degrade events "
                             f"{degrade_log()}")
    _log(f"serve: {len(roots)} queries valid in {ticks} ticks, "
         f"{wall:.2f} s wall; {faults}, no degrade events")


def four_chips() -> None:
    import jax
    import numpy as np

    import repro.bfs as bfs

    g = _graph(SCALE)
    roots = _roots(g, 4)
    mesh = jax.make_mesh((4,), ("x",))
    on_mesh = bfs.plan(g, mesh=mesh)
    one = bfs.plan(g)
    ref = bfs.parents_graph500(one.run_batched(roots).state, g.n_vertices)
    _log(f"mesh {dict(mesh.shape)} over "
         f"{[d.id for d in mesh.devices.flat]}; merge "
         f"{on_mesh.resolved.merge!r}; reference plan on "
         f"{sorted(d.id for d in ref.devices())}")
    for i, root in enumerate(roots):
        t0 = time.perf_counter()
        parent, layers = on_mesh.run(int(root))
        parent.block_until_ready()
        wall = time.perf_counter() - t0
        p = np.where(np.asarray(parent) >= g.n_vertices, -1,
                     np.asarray(parent))
        depth = _check_tree(g, p, root)
        ref_depth = _check_tree(g, ref[i], root)
        if not np.array_equal(np.asarray(depth), np.asarray(ref_depth)):
            raise AssertionError(f"root {root}: mesh depths differ from "
                                 f"the one-chip plan")
        _log(f"mesh root {root}: valid, depths equal the one-chip plan; "
             f"{int(layers)} layers; {wall:.2f} s")
    # the plan's edge partition: each chip must hold its own slice
    rows_sh = on_mesh._partition.rows
    local = {s.device.id: int((np.asarray(s.data) < g.n_vertices).sum())
             for s in rows_sh.addressable_shards}
    if (sorted(local) != sorted(d.id for d in mesh.devices.flat)
            or min(local.values()) == 0
            or sum(local.values()) != g.n_edges):
        raise AssertionError(f"edges per device {local}, expected all "
                             f"{g.n_edges} split over the mesh")
    _log(f"edges per device: {local} (the result is replicated)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro import compile_cache
    cache = compile_cache.enable()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{dev.platform!r}); nothing ran", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    _log(f"device: {dev.device_kind} x{len(devices)}; jax "
         f"{jax.__version__}; compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    _log(f"total: {time.perf_counter() - t0:.2f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
