"""End-to-end metric arithmetic over one window's request log.

Every request due in the window counts.  A request that failed, or never
finished, has an infinite latency and misses any limit.
"""
from __future__ import annotations

import math

import numpy as np


def nearest_rank(values, q: float) -> float:
    """The ``q``-quantile (0 < q <= 1) by nearest rank: the smallest value
    with at least a ``q`` share of the values at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return math.nan
    return float(v[max(math.ceil(q * len(v)) - 1, 0)])


def latencies(due, done, ok) -> np.ndarray:
    """Seconds from due to done; inf where not ``ok`` or never done."""
    due = np.asarray(due, np.float64)
    done = np.asarray([math.inf if d is None else d for d in done],
                      np.float64)
    lat = done - due
    lat[~np.asarray(ok, bool)] = math.inf
    return lat


def qps(done, ok, t0: float, t1: float, rows=None) -> float:
    """Query rows of the successful completions inside ``[t0, t1)`` per
    second (``rows``: per request, default 1)."""
    done = np.asarray([math.nan if d is None else d for d in done],
                      np.float64)
    inside = (done >= t0) & (done < t1) & np.asarray(ok, bool)
    rows = np.ones(len(done)) if rows is None else np.asarray(rows)
    return float(rows[inside].sum()) / (t1 - t0)


def recall(returned: np.ndarray, truth: np.ndarray) -> float:
    """Mean |returned ∩ truth| / k over rows; rows of ``returned`` are
    one answer's ids (``-1`` never matches)."""
    k = truth.shape[1]
    hits = [len(set(r.tolist()) & set(t.tolist()))
            for r, t in zip(np.asarray(returned), np.asarray(truth))]
    return float(np.mean(hits)) / k if hits else math.nan

