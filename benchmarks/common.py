"""Shared benchmark utilities: graph cache, timing, CSV emission.

Every `emit` is also recorded in the in-process ``RESULTS`` registry;
`benchmarks.run` persists that registry to ``BENCH_bfs.json`` at the
repo root after each run
(merge-update, so partial ``--only`` runs refresh just their keys) —
the cross-PR perf trajectory file the CI bytes-moved gate reads.
Since ISSUE 7 the file also carries a ``_meta`` record (git sha,
harness timestamp, jax version, device kind, interpret flag) so a
baseline's provenance is attributable when a gate fails — the PR-5
load-noise incident, made diagnosable."""
from __future__ import annotations

import json
import pathlib
import subprocess
import time

import jax

from repro.core import csr as csr_mod
from repro.core import rmat

_GRAPH_CACHE: dict = {}

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_bfs.json"

#: name -> {"us_per_call": float, "derived": str, "value": float?}
RESULTS: dict[str, dict] = {}


def graph(scale: int, edgefactor: int = 16, seed: int = 2):
    key = (scale, edgefactor, seed)
    if key not in _GRAPH_CACHE:
        _GRAPH_CACHE[key] = csr_mod.from_edges(
            rmat.generate(jax.random.PRNGKey(seed), scale, edgefactor))
    return _GRAPH_CACHE[key]


def time_bfs(fn, csr, roots, warmup_root=None) -> float:
    """Mean seconds per BFS over the given roots (after warmup)."""
    jax.block_until_ready(
        fn(csr, int(warmup_root if warmup_root is not None
                    else roots[0])).parent)
    t0 = time.perf_counter()
    for r in roots:
        jax.block_until_ready(fn(csr, int(r)).parent)
    return (time.perf_counter() - t0) / len(roots)


def emit(name: str, us_per_call: float, derived: str,
         value: float | None = None):
    """The run.py contract: ``name,us_per_call,derived`` CSV.

    ``value`` optionally attaches a machine-readable number (TEPS,
    analytic bytes, tile counts) to the ``RESULTS``/BENCH_bfs.json
    record — what regression gates compare instead of parsing the
    derived string."""
    print(f"{name},{us_per_call:.1f},{derived}")
    rec = {"us_per_call": round(us_per_call, 1), "derived": derived}
    if value is not None:
        rec["value"] = float(value)
    RESULTS[name] = rec


def build_meta(timestamp: str | None = None) -> dict:
    """The ``_meta`` provenance record stamped into BENCH_bfs.json.

    ``timestamp`` is passed in by the harness (one stamp per run, not
    one per call).  Git metadata degrades to "unknown" outside a work
    tree so benchmarks stay runnable from an export."""
    def _git(*args: str) -> str:
        try:
            return subprocess.run(
                ["git", *args], capture_output=True, text=True,
                cwd=pathlib.Path(__file__).resolve().parent,
                timeout=10).stdout.strip() or "unknown"
        except (OSError, subprocess.SubprocessError):
            return "unknown"

    return {
        "git_sha": _git("rev-parse", "--short", "HEAD"),
        "git_dirty": bool(_git("status", "--porcelain")
                          not in ("", "unknown")),
        "timestamp": timestamp or "unknown",
        "jax_version": jax.__version__,
        "device_kind": jax.devices()[0].device_kind,
        "backend": jax.default_backend(),
        "interpret": jax.default_backend() != "tpu",
    }


def save_results(meta: dict | None = None) -> None:
    """Merge ``RESULTS`` into BENCH_bfs.json (sorted, stable diffs).
    ``meta`` (see `build_meta`) replaces the file's ``_meta`` record —
    the underscore prefix keeps it clear of every benchmark key
    namespace (gates and `formats.affinity` look up specific
    prefixes)."""
    data = {}
    if BENCH_JSON.exists():
        data = json.loads(BENCH_JSON.read_text())
    data.update(RESULTS)
    if meta is not None:
        data["_meta"] = meta
    BENCH_JSON.write_text(json.dumps(data, indent=1, sort_keys=True)
                          + "\n")
