"""Open-loop request schedule: when each request is due and its rows.

Adapted from the program's ``core/faults.bursty_arrivals``: exponential
gaps around a base rate, and every ``burst_every``-th arrival opens a
burst of ``burst_len`` requests carrying ``burst_factor`` times the rows
with no gap.  Request rows are lognormal (``rows_median``,
``rows_sigma``), clipped to ``[rows_min, rows_max]``.

Every seed gets the same work: the gaps, the rows of the requests that
open no burst and the rows of the burst requests are each the quantiles
of their distributions at evenly spaced levels, and only their order
within each of the three sets is drawn from the seed.  So the number of
requests, the rows each burst carries, the rows of the whole window and
the offered rate are the same in every run, and the gaps sum to the
window.
"""
from __future__ import annotations

import math

import numpy as np
from scipy.special import ndtri


def _levels(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _rows(mix: dict, n: int, factor: float) -> np.ndarray:
    """The lognormal rows of ``n`` requests at evenly spaced levels,
    times ``factor``, clipped and rounded."""
    base = np.exp(math.log(mix["rows_median"])
                  + mix["rows_sigma"] * ndtri(_levels(n)))
    return np.clip(np.rint(base * factor), mix["rows_min"],
                   mix["rows_max"]).astype(int)


def schedule(mix: dict, seconds: float, seed: int):
    """``[(due_s, rows), ...]`` with due times in ``[0, seconds)``."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    every, blen = int(mix["burst_every"]), int(mix["burst_len"])
    burst = np.array([every > 0 and i >= every and i % every < blen
                      for i in range(n)])
    rng = np.random.default_rng([seed, 2])
    rows = np.zeros(n, int)
    rows[~burst] = rng.permutation(_rows(mix, int((~burst).sum()), 1.0))
    rows[burst] = rng.permutation(_rows(mix, int(burst.sum()),
                                        mix["burst_factor"]))
    n_gaps = int((~burst).sum())
    gaps = -np.log1p(-_levels(n_gaps))
    gaps = rng.permutation(gaps) * (seconds / (gaps.sum() + gaps.mean()))
    due, out, g = 0.0, [], iter(gaps)
    for i in range(n):
        if not burst[i]:
            due += float(next(g))
        out.append((due, int(rows[i])))
    return out
