"""Graph500-style benchmark run — the paper's §5 experimental design.

64 BFS executions from random start vertices on an RMAT graph,
harmonic-mean TEPS, with the Graph500 soft validation on each run —
the end-to-end driver for the paper's kind of system (throughput
benchmark), mirroring Fig. 10.

    PYTHONPATH=src python examples/graph500_bench.py --scale 16 --roots 64
"""
import argparse
import sys
import time

import jax
import numpy as np

from repro import compile_cache
from repro.core import csr as csr_mod
from repro.core import rmat
from repro.core.bfs_parallel import run_bfs
from repro.core.bfs_serial import bfs_serial
from repro.core.bfs_vectorized import run_bfs_vectorized
from repro.core.stats import run_harness


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=14)
    ap.add_argument("--edgefactor", type=int, default=16)
    ap.add_argument("--roots", type=int, default=64)
    ap.add_argument("--validate", action="store_true")
    ap.add_argument("--algorithm", default="vectorized",
                    choices=["vectorized", "simd", "nonsimd"])
    args = ap.parse_args()
    compile_cache.enable()

    print(f"== Graph500 kernel 1: SCALE={args.scale} "
          f"edgefactor={args.edgefactor}")
    t0 = time.perf_counter()
    g = csr_mod.from_edges(
        rmat.generate(jax.random.PRNGKey(2), args.scale,
                      args.edgefactor))
    print(f"   construction: {time.perf_counter()-t0:.1f}s  "
          f"|V|={g.n_vertices:,} |E|={g.n_edges:,}")

    fn = {"vectorized": run_bfs_vectorized,
          "simd": lambda c, r: run_bfs(c, r, algorithm="simd"),
          "nonsimd": lambda c, r: run_bfs(c, r, algorithm="nonsimd"),
          }[args.algorithm]

    ref_fn = None
    if args.validate:
        rows = np.asarray(g.rows)
        cs = np.asarray(g.colstarts)
        ref_fn = lambda root: bfs_serial(rows, cs, g.n_vertices,
                                         root)[1]

    print(f"== Graph500 kernel 2: {args.roots} BFS runs "
          f"({args.algorithm})")
    res = run_harness(g, fn, jax.random.PRNGKey(11),
                      n_roots=args.roots,
                      validate_runs=args.validate,
                      reference_depths_fn=ref_fn)
    if args.validate:
        bad = [r for r in res.runs if r.valid is False]
        assert not bad, f"validation failures: {bad}"
        print("   all runs validated")
    print(f"   {res.summary()}")
    print(f"   harmonic_mean_TEPS {res.hmean_teps:.3e}")
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
