"""Arithmetic shared by the metric readers under `bench/metrics/`."""
from __future__ import annotations

import numpy as np


def latencies(run) -> list[float]:
    """Due-to-harvest seconds of every query due in the window that
    came back (one that never did is counted in ``failed``)."""
    return [q["done"] - q["due"] for q in run.record.queries
            if q["done"] is not None]


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile, linearly interpolated between order
    statistics (NumPy's default); None for no values."""
    return float(np.percentile(values, q)) if len(values) else None


def idle_share(run) -> float | None:
    """Per cent of the traced window in which no operation ran on the
    device."""
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
