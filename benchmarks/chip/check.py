"""The comparison that decides ``correct``.

Every request due in the window is judged by what its answer says, against
the plain reference (``reference.py``):

``bad_answers``   answers that never came, failed, or are malformed: not
                  ``k`` ids, an id outside the data, an id twice, a
                  distance that is not finite, or distances out of order.
                  Limit 0.
``dist_rel_err``  the widest relative gap between a distance the program
                  returned and the float64 distance from the request's own
                  query to the row it named (``reference.true_dists``).  A
                  request handed another request's rows, an altered id, or
                  distances computed in a lower precision all show here.
``recall_miss``   the share of the reference's exact top-``k``
                  (``reference.exact_topk``) that the well-formed answers
                  leave out.  A search that looks at the wrong rows, or at
                  too few (a wrong route, fewer probes, a stage 1 over part
                  of the data or at a coarser code), shows here even where
                  the distances it returns are exact.

Limits come from the configuration file (``correct``); how each was set is
in PERF.md.
"""
from __future__ import annotations

import math

import numpy as np

from reference import true_dists
from stats import recall


def judge(answers, queries: np.ndarray, data: np.ndarray,
          truth: np.ndarray, k: int, limits: dict) -> dict:
    """``answers``: per request ``(dists (k,), ids (k,))`` or ``None``;
    ``queries``: (R, D) the request's query rows; ``truth``: (R, k) the
    reference's exact ids.  Returns ``{name: {"value": v, "limit": l}}``."""
    n = data.shape[0]
    bad = 0
    ids = np.full((len(answers), k), -1, np.int64)
    got = np.full((len(answers), k), np.nan, np.float64)
    for r, ans in enumerate(answers):
        if ans is None:
            bad += 1
            continue
        d, g = (np.asarray(a).reshape(-1) for a in ans)
        if (len(g) != k or len(d) != k or (g < 0).any() or (g >= n).any()
                or len(set(g.tolist())) != k or not np.isfinite(d).all()
                or (np.diff(d) < 0).any()):
            bad += 1
            continue
        ids[r] = g
        got[r] = d
    ok = ~np.isnan(got[:, 0])
    err = miss = math.nan
    if ok.any():
        true = true_dists(data, queries[ok], ids[ok])
        rel = np.abs(got[ok] - true) / np.maximum(true, 1e-30)
        err = float(np.max(rel))
        miss = 1.0 - recall(ids[ok], np.asarray(truth)[ok])
    return {"bad_answers": {"value": bad,
                            "limit": limits["bad_answers"]},
            "dist_rel_err": {"value": err,
                             "limit": limits["dist_rel_err"]},
            "recall_miss": {"value": miss,
                            "limit": limits["recall_miss"]}}


def passed(numbers: dict) -> bool:
    """Every number at or under its limit (NaN never passes)."""
    return all(v["value"] <= v["limit"] for v in numbers.values())
