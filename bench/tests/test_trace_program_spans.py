"""The trace reduction on a TPU v5e trace that holds the program's
own spans.

``data/tpu_spans.xplane.pb.gz``: seven ``GraphEngine`` ticks (8 slots,
twelve queries on an RMAT scale-10 graph, the engine warmed first),
each under a ``bench.step`` annotation, with the engine's ``serve.*``
spans (`repro.obs.span`) nested inside on the same thread.  The
expected numbers were worked out apart from the reducer, as for
``tpu_small``: the window runs from the first ``bench.*`` start
(41,173,649 ns) to the last end (121,152,125 ns), and busy time counts
every nanosecond of it covered by an ``XLA Ops`` event on a boolean
timeline.  Worked out the same way for a reduction that reads the
spans: all 52,595,687 ns of idle lie inside ``serve.tick``, of them
11,949,034 in ``serve.fill``, 12,401,398 in ``serve.readback`` and
28,245,255 in ``serve.harvest`` (gaps placed by their midpoints).
"""
import gzip
import pathlib

import pytest
from jax.profiler import ProfileData

from bench import trace_reduce as tr

TRACE = pathlib.Path(__file__).parent / "data" / "tpu_spans.xplane.pb.gz"
WINDOW_NS = 121_152_125 - 41_173_649
BUSY_NS = 27_382_789
SPAN_COUNTS = {"serve.tick": 7, "serve.fill": 7, "serve.dispatch": 7,
               "serve.readback": 7, "serve.harvest": 8}


def _bench_thread_spans():
    data = ProfileData.from_serialized_xspace(gzip.open(TRACE).read())
    (line,) = [line for plane in data.planes if plane.name == tr.HOST_PLANE
               for line in plane.lines
               if any(e.name.startswith(tr.ANNOTATION_PREFIX)
                      for e in line.events)]
    return [e.name for e in line.events if e.name.startswith("serve.")]


def test_program_spans_leave_the_window_and_its_names_alone():
    names = _bench_thread_spans()
    assert {n: names.count(n) for n in set(names)} == SPAN_COUNTS
    s = tr.reduce_file(str(TRACE))
    assert s.window_s == pytest.approx(WINDOW_NS * 1e-9, abs=1e-12)
    assert s.busy_s == pytest.approx(BUSY_NS * 1e-9, abs=1e-12)
    assert all(name.startswith("bench.step")
               for name, _ in s.breakdown()["idle_gaps"])
    assert sum(sec for _, sec in s.breakdown()["idle_gaps"]) \
        <= s.window_s - s.busy_s + 1e-12
