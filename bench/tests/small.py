"""Cells at a size a CPU test run holds, on the chip's code path (the
query cells at scale 10, where the engine picks SELL as on the chip)."""
from __future__ import annotations

import time

from bench import harness

#: the chip's expansion path (``pipeline="xla"``), forced on the CPU
SPEC = {"pipeline": "xla"}


def config(scale: int = 9, **extra) -> dict:
    cfg = {"name": f"rmat-{scale}", "generator": "rmat", "scale": scale,
           "edgefactor": 16, "structure_seed": scale, "batch_slots": 8,
           "spec": SPEC}
    cfg.update(extra)
    return cfg


def search_cell(scale: int = 9) -> harness.Cell:
    return harness.Cell(
        "test.search", 1, config(scale),
        {"driver": "searches", "batch": 1, "roots": 8},
        ["setup_s", "teps"],
        ["device.idle_share.g500", "engine.expand_s_per_search",
         "engine.expand_roofline_share"])


def query_cell(arrivals: dict, scale: int = 10,
               drain_s: float = 5.0) -> harness.Cell:
    return harness.Cell(
        "test.queries", 1, config(scale),
        {"driver": "queries", "arrivals": arrivals, "root_pool": 64,
         "drain_s": drain_s},
        ["setup_s", "query_p50_s", "query_p95_s", "served_qps"],
        ["serve.tick_s.steady", "serve.slot_occupancy"])


def run(cell: harness.Cell, seed: int = 1, seconds: float = 1.0) -> dict:
    """The result object of one run of ``cell`` on whatever device JAX
    has."""
    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            require_tpu=False)[0]
