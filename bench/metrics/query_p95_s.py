"""The 95th percentile of query latency, from the time each query
was due to its harvest, over every query due in the window."""
from bench import metric_util

UNIT = "s"


def read(run):
    return metric_util.percentile(metric_util.latencies(run), 95)
