"""The one traffic generator: every mix under `bench/traffic/` is
parameters for it.

Randomness comes from ``numpy.random.default_rng([seed, stream])``,
one stream per purpose, so the same seed gives the same roots and
arrivals.  Arrival gaps are the ``n = rate * seconds`` quantiles of
the exponential distribution in a shuffled order, so every window
holds the same load.

Every configuration has a fixed structure that the run's seed
relabels (``fixed``, see a generator's ``for_config`` under
`bench/generators/`), so the work is the same for every seed: the
roots are one set drawn from the structure seed, mapped through the
run's relabelling and put in an order drawn from the run's seed (or
kept in the structure seed's order), and the arrival times are the
structure seed's.  A seed then changes the labels and which query
comes when, not the sizes of the queries or the bursts of the stream.
"""
from __future__ import annotations

import numpy as np

#: stream ids of `rng`
ROOTS, ARRIVALS = 1, 2


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**64, stream])


def roots(seed: int, degrees: np.ndarray, count: int, replace: bool,
          fixed, shuffle: bool = True) -> np.ndarray:
    """``count`` roots drawn uniformly among vertices of degree > 0:
    with ``fixed = (structure_seed, label)``, the structure seed's set
    relabelled, in the seed's order (in the structure seed's own order
    where ``shuffle`` is false)."""
    degrees = np.asarray(degrees)
    structure_seed, label = fixed
    base = rng(structure_seed, ROOTS).choice(
        np.flatnonzero(degrees[label] > 0), count, replace=replace)
    if shuffle:
        base = base[rng(seed, ROOTS).permutation(count)]
    return label[base].astype(np.int32)


def poisson_offsets(structure_seed: int, rate: float,
                    seconds: float) -> np.ndarray:
    """Due times in ``[0, seconds)`` of ``round(rate * seconds)``
    arrivals whose gaps are the exponential quantiles at
    ``(i + 0.5) / n``, in an order drawn from the structure seed."""
    n = int(round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    order = rng(structure_seed, ARRIVALS).permutation(gaps)
    due = np.cumsum(order) - gaps.min()
    return due[due < seconds]
