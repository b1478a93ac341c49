"""The trace reduction, on a trace recorded on a TPU v5e.

``data/tpu_small.xplane.pb.gz``: one search of an RMAT scale-10 graph
through ``plan(g).run_batched`` under a ``bench.search`` annotation,
then three ``GraphEngine`` ticks under ``bench.tick``.  The expected
numbers were worked out apart from the reducer: the window is the
first annotation's start (43,183,469 ns) to the last one's end
(65,288,588 ns); busy time counts every nanosecond of that window
covered by some ``XLA Ops`` event, marked one by one on a boolean
timeline; the expand time sums the events that enclose no other
event and whose ``tf_op`` path has the component ``bfs.expand``.
"""
import pathlib

import pytest

from bench import trace_reduce as tr

TRACE = pathlib.Path(__file__).parent / "data" / "tpu_small.xplane.pb.gz"
WINDOW_NS = 65_288_588 - 43_183_469
BUSY_NS = 14_063_131
EXPAND_NS = 2_103_081


@pytest.fixture(scope="module")
def summary():
    return tr.reduce_file(str(TRACE))


def test_window_and_busy(summary):
    assert summary.window_s == pytest.approx(WINDOW_NS * 1e-9, abs=1e-12)
    assert summary.busy_s == pytest.approx(BUSY_NS * 1e-9, abs=1e-12)
    idle = 100 * (1 - summary.busy_s / summary.window_s)
    assert idle == pytest.approx(100 * (1 - BUSY_NS / WINDOW_NS))


def test_scope_time(summary):
    assert summary.scope_s["bfs.expand"] == pytest.approx(
        EXPAND_NS * 1e-9, abs=1e-12)
    # the layer loop's other scopes are tiny next to the expansion
    assert 0 < summary.scope_s["bfs.measure_decide"] < 1e-4


def test_breakdown(summary):
    b = summary.breakdown()
    assert 1 <= len(b["device_ops"]) <= tr.TOP
    assert 1 <= len(b["idle_gaps"]) <= tr.TOP
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    # ranked by leaf time: no enclosing while/conditional op listed
    assert not any(name.startswith("%while") for name, _ in b["device_ops"])
    # the tick's scatter leads; every gap is named by a bench phase
    assert b["device_ops"][0][0].startswith("jit(_layer)/")
    assert all(name.startswith("bench.") for name, _ in b["idle_gaps"])
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(
        summary.window_s - summary.busy_s, abs=1e-9)


@pytest.mark.parametrize("intervals, lo, hi, union, holes", [
    ([(0, 2), (1, 3), (5, 6)], 0, 6, 4, [(3, 5)]),
    ([(1, 2), (1, 2), (4, 9)], 0, 8, 6, [(0, 1), (2, 4)]),
    ([(0, 10), (2, 3)], 0, 10, 10, []),
    ([], 0, 4, 0, [(0, 4)]),
])
def test_union_and_gaps(intervals, lo, hi, union, holes):
    assert tr.union_length(intervals) == union
    assert tr.gaps(intervals, lo, hi) == holes


def test_scope_is_a_path_component():
    assert tr.has_scope("jit(_run)/while/body/bfs.expand/gather:",
                        "bfs.expand")
    assert not tr.has_scope("jit(_run)/bfs.expanded/gather", "bfs.expand")
