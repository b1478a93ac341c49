"""Per cent of the traced window with no operation on the device:
1 - (union of device op intervals) / window, a mean over the chips,
from the profiler trace."""
from bench import metric_util

UNIT = "%"


def read(run):
    return metric_util.idle_share(run)
