"""The comparison that decides `correct` fails its control and every
fault the cells can have, at a size a CPU test run holds.

The control (`bench.control`) is the reference with its level barrier
taken out, put in the driver's place under a whole run of the harness
(its look for a chip skipped).  The faults are planted in the program underneath a whole
run of the harness (its look for a chip skipped): a step that returns
its state unchanged, a tick that leaves half of the slots out, and an
answer altered where it is produced.  The cells run on one chip, so
there is no exchange between chips to leave out.
"""
import numpy as np
import pytest

import repro.bfs as bfs
from bench import control
from bench.tests import small

CLOSED = {"kind": "closed", "clients": 16}


@pytest.fixture(autouse=True)
def fresh_plans():
    bfs.clear_plan_cache()
    yield
    bfs.clear_plan_cache()


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_fails(seed):
    for cell in (small.search_cell(10), small.query_cell(CLOSED, 10),
                 small.query_cell({"kind": "poisson", "rate_qps": 20.0}, 10)):
        result = control.run(cell, seed, 3, 1.0, require_tpu=False)
        assert not result["correct"]
        assert result["attempted"] == result["failed"] == 3
        wrong = result["checks"]["wrong_vertices"]
        assert wrong["value"] > wrong["limit"]


@pytest.mark.parametrize("cell", ["search", "queries"])
def test_sound_run_is_correct(cell):
    c = small.search_cell() if cell == "search" else small.query_cell(CLOSED)
    result = small.run(c)
    assert result["correct"], result
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"


def _unchanged_steps(monkeypatch):
    from repro.core import engine
    real = engine.make_xla_steps

    def frozen(*args, **kw):
        def same(frontier, visited, parent):
            _, _, _, aux = steps[engine.MODE_SIMD](frontier, visited, parent)
            return frontier, visited, parent, aux
        steps = real(*args, **kw)
        return {mode: same for mode in steps}
    monkeypatch.setattr(engine, "make_xla_steps", frozen)


def test_search_step_returns_state_unchanged(monkeypatch):
    _unchanged_steps(monkeypatch)
    result = small.run(small.search_cell())
    assert not result["correct"]
    assert result["checks"]["wrong_vertices"]["value"] > 0


def test_search_answer_altered(monkeypatch):
    real = bfs.parents_graph500

    def altered(state, n_vertices):
        p = np.asarray(real(state, n_vertices)).copy()
        reached = np.flatnonzero(p[0] >= 0)
        p[0, reached[-1]] = p[0, reached[0]] + 1
        return p
    monkeypatch.setattr(bfs, "parents_graph500", altered)
    result = small.run(small.search_cell())
    assert not result["correct"]
    assert result["checks"]["wrong_vertices"]["value"] >= 1


def test_tick_returns_state_unchanged(monkeypatch):
    _unchanged_steps(monkeypatch)
    result = small.run(small.query_cell(CLOSED))
    assert not result["correct"]
    assert result["checks"]["unanswered"]["value"] > 0


def test_tick_leaves_half_the_slots_out(monkeypatch):
    from repro.api.plan import CompiledTraversal
    real = CompiledTraversal.layer_step

    def half(self, frontier, visited=None, parent=None):
        new = real(self, frontier, visited, parent)
        keep = frontier.shape[0] // 2
        return tuple(n.at[keep:].set(o[keep:])
                     for n, o in zip(new, (frontier, visited, parent)))
    monkeypatch.setattr(CompiledTraversal, "layer_step", half)
    result = small.run(small.query_cell(CLOSED))
    assert not result["correct"]
    assert result["checks"]["unanswered"]["value"] > 0


def test_served_answer_altered(monkeypatch):
    from repro.serve.graph_engine import GraphEngine
    real = GraphEngine._harvest

    def altered(self, i, q, *args, **kw):
        ok = real(self, i, q, *args, **kw)
        if ok and q.parent is not None:
            reached = np.flatnonzero(q.parent >= 0)
            q.parent = q.parent.copy()
            q.parent[reached[-1]] = (q.parent[reached[-1]] + 1) \
                % len(q.parent)
        return ok
    monkeypatch.setattr(GraphEngine, "_harvest", altered)
    result = small.run(small.query_cell(CLOSED))
    assert not result["correct"]
    assert result["checks"]["wrong_vertices"]["value"] > 0
