"""Run one benchmark cell and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json`` names its configuration (``bench/configs/``) and
its traffic mix (``bench/traffic/<traffic>.json``); the configuration
names its graph generator (``bench/generators/<generator>.py``), the
mix its driver (``bench/drivers/<driver>.py``); each metric the cell
reports is read by ``bench/metrics/<metric>.py``.  A new cell, mix,
generator, driver or metric is a new file and a new entry, never an
edit.

A run: make the graph on the device from the seed, build the
program's CSR from it, let the driver set up and warm its entry,
measure for ``--seconds`` (under the profiler with ``--trace 1``),
then, with the program's state freed, compare every BFS tree the
window produced with the plain reference (`bench.reference`).  The
last line of standard output is one JSON object; the numbers
compared, each beside its limit, are also the last lines of
standard error.  With no TPU, or fewer chips than the cell asks
for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

#: a missing accelerator: no result line, this exit code
NO_DEVICE = 2
#: JAX's event around each backend compile or persistent-cache load
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path (metric and driver
    files are named after dotted metric names)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[str]
    per_layer: list[str]


def _reports(metric: dict, cell: str, e2e_of_cell: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # a per-layer metric without the key goes with its end-to-end one
    return metric.get("moves", metric["name"]) in e2e_of_cell


def load_cell(name: str, benchmark: pathlib.Path | None = None) -> Cell:
    spec = json.loads((benchmark or ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((ROOT / cfg["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m["name"] for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    per_layer = [m["name"] for m in spec["per_layer"]
                 if _reports(m, name, set(e2e))]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer)


class Window:
    """The measured window's clock, compile count and profiler.

    `open` ends set-up; `close` ends the window.  With tracing on, the
    profiler records from `open` to `close`, and `annotate` marks the
    host's phases in that trace so that idle gaps can be named."""

    def __init__(self, t_start: float, trace: bool):
        import jax
        self.t_start = t_start
        self.trace = trace
        self.t_open = self.t_close = None
        self.trace_dir: str | None = None
        self.compiles = 0
        self.compiles_setup = self.compiles_window = None
        jax.monitoring.register_event_duration_secs_listener(
            self._on_compile)

    def _on_compile(self, event: str, _secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1

    def open(self) -> float:
        import jax
        self.compiles_setup = self.compiles
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.trace_dir)
        self.t_open = time.perf_counter()
        return self.t_open

    def close(self) -> float:
        import jax
        self.t_close = time.perf_counter()
        if self.trace:
            jax.profiler.stop_trace()
        self.compiles_window = self.compiles - self.compiles_setup
        jax.monitoring.unregister_event_duration_listener(self._on_compile)
        return self.t_close

    @property
    def setup_s(self) -> float:
        return self.t_open - self.t_start

    def annotate(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Context:
    """What a driver is handed: the cell, the graph, the clock."""
    cell: Cell
    seed: int
    seconds: float
    graph: Any              # the program's Csr
    edges: tuple            # the benchmark's own (src, dst) on the device
    degrees: np.ndarray     # the benchmark's own degree count
    fixed: tuple            # (structure seed, relabelling)
    window: Window


@dataclasses.dataclass
class Record:
    """What a driver hands back once its device state is freed.

    ``trees`` holds ``(root, parent)`` pairs (Graph500 convention) of
    every answer due in the window; ``unanswered`` counts answers due
    that never came whole (never harvested, truncated, an error)."""
    window_s: float
    attempted: int
    trees: list = dataclasses.field(default_factory=list)
    unanswered: int = 0
    searches: list = dataclasses.field(default_factory=list)
    queries: list = dataclasses.field(default_factory=list)
    tick_mean_s: float | None = None
    tick_ends: list = dataclasses.field(default_factory=list)
    occupancy: list = dataclasses.field(default_factory=list)
    notes: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    cell: Cell
    setup_s: float
    record: Record
    n_vertices: int
    n_slots: int
    device_kind: str
    trace: Any = None       # bench.trace_reduce.Summary with --trace 1


def device_check(chips: int):
    """The devices of the run, or None where JAX finds no TPU or
    fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: JAX found no TPU (platform "
            f"{devices[0].platform!r}); nothing measured")
        return None
    if len(devices) < chips:
        log(f"bench: the cell needs {chips} chips, JAX found "
            f"{len(devices)}")
        return None
    return devices


def generator(name: str):
    """The graph generator ``bench/generators/<name>.py``: its
    ``for_config(config, seed)`` returns ``(src, dst, n_vertices,
    fixed)``, the symmetrized edge list on the device and
    ``fixed = (structure_seed, relabelling)``."""
    return load_module(BENCH / "generators" / f"{name}.py")


def _make_graph(config: dict, seed: int):
    """The benchmark's edge list from the seed (the configuration's
    generator), its degrees, and the program's CSR built from it."""
    import jax
    import jax.numpy as jnp

    from repro.core import csr
    from repro.core.rmat import EdgeList
    src, dst, v, fixed = generator(config["generator"]).for_config(
        config, seed)
    degrees = np.asarray(jnp.bincount(src, length=v))
    g = csr.from_edges(EdgeList(src, dst, v))
    jax.block_until_ready(g.rows)
    return (src, dst), degrees, fixed, g


def check(record: Record, edges, n_vertices: int) -> dict:
    """The numbers compared, ``{name: (value, limit)}``, over every
    tree due in the window: the vertices whose parent breaks the BFS
    tree rule (`bench.reference.wrong_vertices`) and the answers that
    never came whole."""
    from bench import reference
    hg = reference.host_graph(np.asarray(edges[0]), np.asarray(edges[1]),
                              n_vertices)
    levels: dict[int, np.ndarray] = {}
    wrong = bad_trees = 0
    for root, parent in record.trees:
        if root not in levels:
            levels[root] = reference.bfs_levels(hg, root)
        n = reference.wrong_vertices(hg, parent, root, levels[root])
        wrong += n
        bad_trees += n > 0
    record.notes.update(trees_compared=len(record.trees),
                        trees_wrong=bad_trees)
    return {"wrong_vertices": (wrong, 0),
            "unanswered": (record.unanswered, 0)}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True, drive=None):
    """One run of ``cell``: ``(result object, notes)``, or None where
    the device check fails.  Tests pass ``require_tpu=False`` to drive
    a run on whatever device JAX has; ``drive`` (a Context -> Record
    callable) puts something else in the place of the cell's driver,
    as `bench.control` puts the control."""
    import jax
    devices = device_check(cell.chips) if require_tpu else jax.devices()
    if devices is None:
        return None
    devices = devices[:cell.chips]
    window = Window(t_start, trace)
    edges, degrees, fixed, graph = _make_graph(cell.config, seed)
    t_graph = time.perf_counter()
    n_vertices, n_slots = graph.n_vertices, graph.n_edges
    if drive is None:
        drive = load_module(
            BENCH / "drivers" / f"{cell.traffic['driver']}.py").run
    record = drive(Context(cell, seed, seconds, graph, edges, degrees,
                           fixed, window))
    del graph
    gc.collect()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    summary = None
    if trace:
        from bench import trace_reduce
        summary = trace_reduce.reduce_dir(window.trace_dir)
        shutil.rmtree(window.trace_dir, ignore_errors=True)
    checks = check(record, edges, n_vertices)
    del edges
    run = Run(cell, window.setup_s, record, n_vertices, n_slots,
              devices[0].device_kind, summary)
    metrics = {}
    for name in cell.per_layer if trace else cell.end_to_end:
        reader = load_module(BENCH / "metrics" / f"{name}.py")
        value = reader.read(run)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": reader.UNIT}
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices),
           "memory_peak_bytes": peak}
    result = {"correct": record.attempted > 0 and all(
                  v <= lim for v, lim in checks.values()),
              "attempted": record.attempted,
              "failed": record.notes["trees_wrong"] + record.unanswered,
              "metrics": metrics, "device": dev}
    if summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    notes = dict(record.notes, setup_s=window.setup_s,
                 setup_to_graph_s=t_graph - t_start,
                 compiles_setup=window.compiles_setup,
                 compiles_window=window.compiles_window)
    return result, notes


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    from repro import compile_cache
    compile_cache.enable()
    import jax
    # every program, however quick to compile, comes from the cache
    # after a cell's first run, so set-up repeats from run to run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start)
    if out is None:
        return NO_DEVICE
    result, notes = out
    for k, v in notes.items():
        print(f"note {k}: {v}", flush=True)
    for k, c in result["checks"].items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0
