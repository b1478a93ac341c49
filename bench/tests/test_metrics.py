"""Metric arithmetic and traffic generation, on hand-made records."""
import types

import numpy as np
import pytest

from bench import harness, traffic, work

METRICS = harness.BENCH / "metrics"


def _read(name, run):
    return harness.load_module(METRICS / f"{name}.py").read(run)


def _run(**record):
    return types.SimpleNamespace(record=harness.Record(**record),
                                 trace=None, setup_s=3.5,
                                 n_vertices=1 << 20, n_slots=1 << 25,
                                 device_kind="TPU v5 lite")


def test_search_edges_is_half_the_reached_degree_sum():
    degrees = np.array([3, 2, 0, 5, 1])
    parent = np.array([0, 0, -1, 1, -1])     # 0, 1 and 3 reached
    assert work.search_edges(degrees, parent) == (3 + 2 + 5) // 2


def test_teps_is_window_edges_over_window_time():
    searches = [{"dispatch": 10.0, "ready": 20.0, "edges": 1000},
                {"dispatch": 20.5, "ready": 30.0, "edges": 3000}]
    run = _run(window_s=20.0, attempted=2, searches=searches)
    # 4000 edges from the first dispatch (10.0) to the last ready (30.0)
    assert _read("teps", run) == pytest.approx(4000 / 20.0)
    assert _read("teps", _run(window_s=1.0, attempted=0)) is None


def _queries(pairs):
    return [{"uid": i, "root": 0, "due": due, "sent": due + 0.25,
             "done": done, "whole": done is not None}
            for i, (due, done) in enumerate(pairs)]


def test_latency_runs_from_due_not_from_submit():
    run = _run(window_s=10.0, attempted=1,
               queries=_queries([(1.0, 4.0)]))
    # sent 0.25 s late: the wait counts
    assert _read("query_p50_s", run) == pytest.approx(3.0)


def test_p95_over_every_query_due():
    pairs = [(float(i), float(i) + 1.0 + (i == 19) * 9.0)
             for i in range(20)]
    run = _run(window_s=30.0, attempted=20, queries=_queries(pairs))
    lat = [1.0] * 19 + [10.0]
    assert _read("query_p95_s", run) == pytest.approx(np.percentile(lat, 95))
    assert _read("query_p50_s", run) == pytest.approx(1.0)


def test_served_qps_counts_whole_answers_inside_the_window():
    # ticks end at 2, 4, 6, 8, 9.5 and 11 s; the window is 10 s long
    qs = _queries([(0.0, 4.0), (0.0, 8.0), (1.0, 11.0), (2.0, 11.0),
                   (2.0, None)])
    for q, (tick, layers) in zip(qs, [(2, 2), (4, 4), (6, 4), (6, 3),
                                      (None, 0)]):
        q.update(tick=tick, layers=layers)
    qs[1]["whole"] = False                  # truncated: not served
    run = _run(window_s=10.0, attempted=5, queries=qs,
               tick_ends=[2.0, 4.0, 6.0, 8.0, 9.5, 11.0])
    # five ticks end inside: the first query whole, 3 of the third's 4
    # ticks (3-5), 2 of the fourth's 3 (4-5); over the 9.5 s they took
    assert _read("served_qps", run) == pytest.approx(
        (1 + 3 / 4 + 2 / 3) / 9.5)
    assert _read("served_qps", _run(window_s=1.0, attempted=1,
                                    queries=qs[:1])) is None


def test_tick_and_occupancy_readers():
    run = _run(window_s=10.0, attempted=1, tick_mean_s=0.75,
               occupancy=[1.0, 0.5, 0.75, 0.75])
    assert _read("serve.tick_s.saturate", run) == 0.75
    assert _read("serve.slot_occupancy", run) == pytest.approx(75.0)


def test_bytes_per_search():
    # Graph500 scale 20: 33,554,432 directed slots, 2**20 vertices
    assert work.bytes_per_search(1 << 20, 1 << 25) \
        == 4 * 33_554_432 + 8 * 1_048_576


def test_roofline_share_from_the_trace():
    run = _run(window_s=20.0, attempted=2, searches=[{}, {}])
    run.trace = types.SimpleNamespace(scope_s={"bfs.expand": 2.0},
                                      busy_s=19.0, window_s=20.0)
    least = (4 * (1 << 25) + 8 * (1 << 20)) / 819e9
    assert _read("engine.expand_s_per_search", run) == pytest.approx(1.0)
    assert _read("engine.expand_roofline_share", run) \
        == pytest.approx(100 * least / 1.0)
    assert _read("device.idle_share.g500", run) == pytest.approx(5.0)


def test_readers_are_silent_without_a_trace():
    run = _run(window_s=20.0, attempted=1, searches=[{}])
    for name in ("engine.expand_roofline_share",
                 "engine.expand_s_per_search", "device.idle_share.g500"):
        assert _read(name, run) is None


def test_missing_device_kind_is_an_error():
    assert work.peak("TPU v5 lite") == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        work.peak("TPU v4")


@pytest.mark.parametrize("seed", [1, 7, 2**31 + 11])
def test_poisson_offsets_hold_the_same_gaps_in_another_order(seed):
    base = traffic.poisson_offsets(0, 1.5, 51.0)
    due = traffic.poisson_offsets(seed, 1.5, 51.0)
    assert len(due) == len(base) == round(1.5 * 51)
    assert np.all(np.diff(due) > 0) and due[0] >= 0 and due[-1] < 51
    n = len(due)
    smallest = -np.log1p(-0.5 / n) / 1.5

    def gaps(d):            # the first gap is shifted by the smallest
        return np.sort(np.concatenate([[d[0] + smallest], np.diff(d)]))
    np.testing.assert_allclose(gaps(due), gaps(base), rtol=1e-9)
    assert not np.allclose(due, base)
    np.testing.assert_array_equal(traffic.poisson_offsets(seed, 1.5, 51.0),
                                  due)


def test_roots_have_degree_and_repeat_per_seed():
    degrees = np.array([0, 4, 0, 1, 2, 0, 3])
    fixed = (9, np.array([3, 0, 6, 2, 5, 1, 4]))
    a = traffic.roots(5, degrees, 50, True, fixed)
    assert set(a.tolist()) <= {1, 3, 4, 6}
    np.testing.assert_array_equal(a, traffic.roots(5, degrees, 50, True,
                                                   fixed))


def test_fixed_structure_gives_every_seed_the_same_work():
    from bench import reference
    config = {"scale": 8, "edgefactor": 16, "structure_seed": 3}
    runs = []
    for seed in (1, 2**31 + 5):
        src, dst, v, fixed = harness.generator("rmat").for_config(
            config, seed)
        g = reference.host_graph(np.asarray(src), np.asarray(dst), v)
        r = traffic.roots(seed, g.degrees, 20, True, fixed)
        depths = sorted(int(reference.bfs_levels(g, int(x)).max())
                        for x in r)
        due = traffic.poisson_offsets(fixed[0], 2.0, 10.0)
        runs.append((np.sort(g.degrees), depths, due, r, src))
    (deg_a, dep_a, due_a, r_a, src_a), (deg_b, dep_b, due_b, r_b, src_b) = runs
    np.testing.assert_array_equal(deg_a, deg_b)     # isomorphic graphs
    assert dep_a == dep_b                           # the same queries
    np.testing.assert_array_equal(due_a, due_b)     # the same arrivals
    assert not np.array_equal(np.asarray(src_a), np.asarray(src_b))
    assert not np.array_equal(r_a, r_b)             # other labels, order


def test_unshuffled_roots_keep_the_structure_order():
    config = {"scale": 8, "edgefactor": 16, "structure_seed": 3}
    structure = []
    for seed in (1, 2**31 + 5):
        src, _, _, fixed = harness.generator("rmat").for_config(
            config, seed)
        degrees = np.bincount(np.asarray(src), minlength=256)
        r = traffic.roots(seed, degrees, 20, False, fixed, shuffle=False)
        inverse = np.argsort(fixed[1])          # vertex -> structure id
        structure.append(inverse[r])
        assert set(r.tolist()) == set(
            traffic.roots(seed, degrees, 20, False, fixed).tolist())
    np.testing.assert_array_equal(*structure)
