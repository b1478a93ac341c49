"""Quickstart: the paper's pipeline end to end on a laptop-size graph.

Generates a Graph500 RMAT graph, then drives everything through the
declarative API (`repro.bfs`): each paper variant (serial oracle
aside) is ONE `TraversalSpec`, planned once (`bfs.plan` — autos
resolved against the graph, one cached jit executable) and run for
many roots.  Validates every tree and prints the TEPS comparison
table the paper's Fig. 9/10 are built from.

    PYTHONPATH=src python examples/quickstart.py [--scale 14]
"""
import argparse
import sys
import time

import jax
import numpy as np

from repro import compile_cache
import repro.bfs as bfs
from repro.core import csr as csr_mod
from repro.core import rmat
from repro.core.bfs_parallel import parents_graph500
from repro.core.bfs_serial import bfs_serial
from repro.core.stats import run_harness
from repro.core.validate import validate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--edgefactor", type=int, default=16)
    ap.add_argument("--roots", type=int, default=8)
    args = ap.parse_args()
    compile_cache.enable()

    print(f"== Graph500 RMAT: SCALE={args.scale} "
          f"edgefactor={args.edgefactor}")
    t0 = time.perf_counter()
    edges = rmat.generate(jax.random.PRNGKey(42), args.scale,
                          args.edgefactor)
    g = csr_mod.from_edges(edges)
    print(f"   |V|={g.n_vertices:,} |E|={g.n_edges:,} "
          f"(built in {time.perf_counter()-t0:.1f}s)")

    root = 1
    while int(g.out_degree(root)) == 0:
        root += 1

    print(f"== serial oracle (Algorithm 1), root={root}")
    p_ref, d_ref = bfs_serial(np.asarray(g.rows), np.asarray(g.colstarts),
                              g.n_vertices, root)
    print(f"   reached {int((d_ref >= 0).sum()):,} vertices, "
          f"depth {int(d_ref.max())}")

    # each paper variant is one declarative spec; plan once, run many
    specs = {
        "nonsimd (Alg. 2)": bfs.TraversalSpec(policy="topdown",
                                              algorithm="nonsimd"),
        "bitmap+restoration (Alg. 3)": bfs.TraversalSpec(
            policy="topdown"),
        "vectorized kernels (§4)": bfs.TraversalSpec(
            policy="threshold_simd"),
        "hybrid (beyond paper)": bfs.TraversalSpec(policy="beamer"),
    }
    plans = {name: bfs.plan(g, spec) for name, spec in specs.items()}
    for name, ct in plans.items():
        state = ct.run(root).state
        p = parents_graph500(state, g.n_vertices)
        res = validate(g, p, root, reference_depth=d_ref)
        assert res.ok, f"{name}: validation failed: {res}"
        print(f"   [valid] {name}")

    auto = bfs.plan(g)          # every field "auto", resolved once
    print(f"== auto plan resolves to: {auto.resolved.to_dict()}")

    print(f"== TEPS harness ({args.roots} random roots, harmonic mean)")
    for name, ct in plans.items():
        h = run_harness(g, lambda c, r, ct=ct: ct.run(r).state,
                        jax.random.PRNGKey(7), n_roots=args.roots)
        print(f"   {name:32s} {h.summary()}")
    print(f"   plan cache: {bfs.plan_cache_info()} — every harness "
          f"root reused its plan's one trace")

    print("== graph formats (§4.2's layout axis, repro/formats)")
    from repro.formats import autotune, registry
    fmts = {name: registry.get(name).from_graph(g)
            for name in ("csr", "sell")}
    base = fmts["csr"].footprint().total_bytes
    fmt_spec = bfs.TraversalSpec(policy="threshold_simd")
    for name, fmt in fmts.items():
        fp = fmt.footprint()
        extra = (f" fill={fmt.fill_ratio:.2f} slices_of_128"
                 if name == "sell" else "")
        print(f"   {fp.summary()}  ({fp.total_bytes/base:.2f}x csr)"
              f"{extra}")
        state = bfs.plan(fmt, fmt_spec).run(root).state
        res = validate(g, parents_graph500(state, g.n_vertices), root,
                       reference_depth=d_ref)
        assert res.ok, f"format {name}: validation failed: {res}"
    choice = autotune.choose(g)
    print(f"   autotuner picks [{choice.format}]: {choice.reason}")

    print(f"== batched multi-root engine ({args.roots} roots, 1 launch)")
    roots = [root + i for i in range(args.roots)]
    ct = plans["bitmap+restoration (Alg. 3)"]
    t0 = time.perf_counter()
    res = ct.run_batched(roots)
    jax.block_until_ready(res.state.parent)
    dt = time.perf_counter() - t0
    # depths counts active layers (= eccentricity + 1 from the root)
    print(f"   {args.roots} searches in {dt:.2f}s "
          f"({args.roots/dt:.1f} roots/s), max tree depth "
          f"{(np.asarray(res.depths) - 1).tolist()}")
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
