"""The comparison that decides ``correct``: served answers against the exact
reference (:mod:`chipbench.reference`), as numbers each held to a limit.

An answer is a request's served list of ``(name, join, sum_b, corr)``.
The planted tables of a request are truly joinable and correlated with it,
so the reference knows what must be served and what each served estimate
must say.  The numbers, each the worst over the requests compared:

* ``planted_missed``: share of the planted tables missing from their
  request's answer (the scan, scoring, top-k and the host re-rank);
* ``join_err``: ``|join - exact| / (||mult_a|| ||mult_b||)`` over the
  served planted tables, the paper's normalized error (the sketches and
  the scan);
* ``sum_err``: ``|sum_b - exact| / (||mult_a|| ||vsum_b||)``, the same
  inner product's Cauchy-Schwarz scale, over the served planted tables;
* ``corr_err``: ``|corr - exact|`` over the served planted tables.

A served name that is not in the lake reads as an infinite ``join_err``.
"""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from chipbench import reference

NUMBERS = ("planted_missed", "join_err", "sum_err", "corr_err")

Answer = List[Tuple[str, float, float, float]]


def compare(queries: Sequence, answers: Sequence[Answer],
            planted: Sequence[Sequence[str]],
            lookup: Callable[[str], "tuple | None"]) -> Dict[str, float]:
    """Readings of every number over the given requests.  ``lookup(name)``
    returns a lake table's raw ``(keys, values)``, or None."""
    missed = total = 0
    worst = {"join_err": 0.0, "sum_err": 0.0, "corr_err": 0.0}
    for query, answer, mine in zip(queries, answers, planted):
        if any(lookup(a[0]) is None for a in answer):
            worst["join_err"] = math.inf
        mine = set(mine)
        hits = [a for a in answer if a[0] in mine]
        missed += len(mine) - len(hits)
        total += len(mine)
        if not hits:
            continue
        ex = reference.join_stats(query, [lookup(a[0]) for a in hits])
        got = np.array([a[1:] for a in hits], np.float64)
        errs = {
            "join_err": np.abs(got[:, 0] - ex.join) / (ex.norm_a
                                                       * ex.norm_b),
            "sum_err": np.abs(got[:, 1] - ex.sum_b) / (ex.norm_a
                                                       * ex.vnorm_b),
            "corr_err": np.abs(got[:, 2] - ex.corr),
        }
        for k, e in errs.items():
            worst[k] = max(worst[k], float(e.max()))
    return {"planted_missed": missed / total if total else 0.0, **worst}


def verdict(readings: Dict[str, float], limits: Dict[str, float],
            failed: int, planted_checked: int) -> bool:
    return (failed == 0 and planted_checked > 0
            and all(readings[k] <= limits[k] for k in NUMBERS))


def lines(readings: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """``{number: {"value", "limit"}}``, the result line's last key."""
    return {k: {"value": readings[k], "limit": limits[k]} for k in NUMBERS}
