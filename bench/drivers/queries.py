"""BFS queries served by `repro.serve.graph_engine.GraphEngine`.

One loop serves both kinds of arrivals of a traffic mix:

* ``{"kind": "poisson", "rate_qps": r}``: an open loop; due times
  from `bench.traffic.poisson_offsets`, sent when due whatever is in
  flight.
* ``{"kind": "closed", "clients": n}``: n clients, each sending its
  next query when its last one is harvested.

Each turn of the loop sends what is due, then runs one ``eng.step()``
(one tick) while any query is queued or in a slot, and otherwise
sleeps until the next arrival.  A query's latency runs from its due
time to the end of the tick that harvested it.  The window is
``--seconds`` long; no query is sent after it, and those due in it
are served to the end for at most ``drain_s`` more seconds.

An open loop draws one root per arrival; a closed loop takes its
roots in turn from a pool of ``root_pool`` (`roots`).  The record
keeps the end of every tick and, for each query, the tick that
harvested it and its layers (one tick each), from which
``served_qps`` counts the share of each query's ticks that fell in the
window.

Set-up builds the engine (its own format choice; the configuration's
optional ``spec`` holds `TraversalSpec` fields) and warms every
slot: each gets a vertex of degree 0 as root, whose query ends in
the one tick that warms the tick program, the slot refill and the
harvest.  Roots are drawn among vertices of degree > 0
(`bench.traffic.roots`).
"""
from __future__ import annotations

import heapq
import time

import numpy as np

from bench import traffic as gen


def _warm(eng, degrees, n_slots: int) -> None:
    from repro.serve.graph_engine import BfsQuery
    isolated = np.flatnonzero(np.asarray(degrees) == 0)
    warm = isolated[:n_slots] if len(isolated) >= n_slots \
        else np.flatnonzero(np.asarray(degrees) > 0)[:n_slots]
    for k, root in enumerate(warm):
        eng.submit(BfsQuery(uid=-1 - k, root=int(root)))
    eng.run_until_done()
    eng.finished.clear()


def _due(ctx) -> list[float]:
    """Due times of the open loop's arrivals, or the closed loop's
    clients' first sends (all at the window's open)."""
    arrivals = ctx.cell.traffic["arrivals"]
    if arrivals["kind"] == "closed":
        return [0.0] * int(arrivals["clients"])
    return gen.poisson_offsets(ctx.fixed[0], float(arrivals["rate_qps"]),
                               ctx.seconds).tolist()


def roots(ctx) -> np.ndarray:
    """The pool the queries take their roots from, in turn."""
    closed = ctx.cell.traffic["arrivals"]["kind"] == "closed"
    n = int(ctx.cell.traffic["root_pool"]) if closed else len(_due(ctx))
    return gen.roots(ctx.seed, ctx.degrees, n, replace=True,
                     fixed=ctx.fixed)


def run(ctx):
    from bench.harness import Record
    from repro.api.spec import TraversalSpec
    from repro.obs.metrics import MetricsRegistry
    from repro.serve.graph_engine import BfsQuery, GraphEngine

    cfg, params = ctx.cell.config, ctx.cell.traffic
    seconds = ctx.seconds
    drain_s = float(params["drain_s"])
    arrivals = params["arrivals"]
    slots = int(cfg["batch_slots"])
    registry = MetricsRegistry()
    spec = cfg.get("spec")
    eng = GraphEngine(ctx.graph, batch_slots=slots, registry=registry,
                      spec=spec and TraversalSpec(**spec))
    _warm(eng, ctx.degrees, slots)

    closed = arrivals["kind"] == "closed"
    due = _due(ctx)
    pool = roots(ctx)
    pending = [(t, uid) for uid, t in enumerate(due)]
    heapq.heapify(pending)
    queries: dict[int, dict] = {}
    next_uid = len(due)
    occupancy = registry.gauge("serve.slot_occupancy")
    tick_hist = registry.histogram("serve.tick_s")
    tick_count0, tick_sum0 = tick_hist.count, tick_hist.sum
    tick_mean = None
    occupancy_samples = []
    tick_ends = []
    harvested = 0
    in_flight = 0

    win = ctx.window
    t0 = win.open()
    window_open = True
    while True:
        now = time.perf_counter() - t0
        if window_open and now >= seconds:
            win.close()
            window_open = False
            n_ticks = tick_hist.count - tick_count0
            if n_ticks:
                tick_mean = (tick_hist.sum - tick_sum0) / n_ticks
        if now >= seconds + drain_s:
            break
        with win.annotate("bench.submit"):
            while pending and pending[0][0] <= min(now, seconds):
                t_due, uid = heapq.heappop(pending)
                root = int(pool[uid % len(pool)])
                eng.submit(BfsQuery(uid=uid, root=root))
                queries[uid] = {"uid": uid, "root": root, "due": t_due,
                                "sent": now, "done": None, "whole": False,
                                "parent": None, "layers": 0, "tick": None}
                in_flight += 1
        if in_flight == 0:
            if not pending or pending[0][0] >= seconds:
                if now >= seconds:
                    break
                wait = seconds - now
            else:
                wait = pending[0][0] - now
            with win.annotate("bench.idle"):
                time.sleep(max(wait, 0.0))
            continue
        with win.annotate("bench.step"):
            eng.step()
        t_done = time.perf_counter() - t0
        tick_ends.append(t_done)
        if window_open:
            occupancy_samples.append(occupancy.value)
        with win.annotate("bench.harvest"):
            for q in eng.finished[harvested:]:
                whole = not q.truncated and q.error is None \
                    and q.parent is not None
                queries[q.uid].update(done=t_done, whole=whole,
                                      parent=q.parent, layers=q.n_layers,
                                      tick=len(tick_ends))
                in_flight -= 1
                if closed and t_done < seconds:
                    heapq.heappush(pending, (t_done, next_uid))
                    next_uid += 1
            harvested = len(eng.finished)
    if window_open:
        win.close()
    fmt = f"{eng.fmt.name}, {eng.fmt.edge_slots} slots"
    del eng
    queries = list(queries.values())

    answered = [q for q in queries if q["done"] is not None and q["whole"]]
    record = Record(window_s=seconds, attempted=len(queries),
                    unanswered=len(queries) - len(answered),
                    tick_mean_s=tick_mean, tick_ends=tick_ends,
                    occupancy=occupancy_samples)
    record.queries = [{k: q[k] for k in ("uid", "root", "due", "sent",
                                         "done", "whole", "layers", "tick")}
                      for q in queries]
    record.trees = [(q["root"], q["parent"]) for q in answered]
    late = [q["sent"] - q["due"] for q in queries]
    record.notes.update(
        queries_due=len(queries), queries_answered=len(answered),
        queries_done_in_window=sum(q["done"] is not None
                                   and q["done"] < seconds
                                   for q in answered),
        generator_late_mean_s=float(np.mean(late)) if late else 0.0,
        generator_late_max_s=float(np.max(late)) if late else 0.0,
        format=fmt, ticks_in_window=sum(t <= seconds for t in tick_ends),
        layers_per_query=float(np.mean([q["layers"] for q in answered]))
        if answered else 0.0)
    return record

