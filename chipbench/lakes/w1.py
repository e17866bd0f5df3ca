"""W1: open-data join-correlation search (Santos et al., SIGMOD 2021).

The background lake is ``chip_smoke.make_lake``'s, copied and driven by the
configuration's numbers: a table draws its keys from one shared domain
(days, zip codes, borough IDs, a cold entity domain); day and zip tables
cover a contiguous range with 1-3 rows per key, the others draw keys at
random; values sit at open-data levels (log-uniform over five decades,
spread 1-50% of the level) and are independent of everything.

A query is a day- or zip-keyed series, one row per key over a contiguous
range, at a level of 10^2-10^5 with a 5% spread of independent draws (so
two queries do not correlate by chance the way random walks do).  Its
planted tables repeat the query's keys 1-3 times, with values at their own
level that follow the query's series.
"""
from __future__ import annotations

import numpy as np

from . import Tables, from_lists, heavy_tail

DOMAIN_SHIFT = 26              # disjoint domains, keys < 2^31


def _domains(params):
    return params["domains"]


def _domain_index(params, name: str) -> int:
    return [d["name"] for d in _domains(params)].index(name)


def background_rows(params, rng, n: int) -> np.ndarray:
    return heavy_tail(rng, n, params["rows_min"], params["rows_cap"],
                      params["rows_alpha"])


def background(params, rng, rows: np.ndarray, prefix: str = "t") -> Tables:
    doms = _domains(params)
    n = rows.size
    sizes = np.array([d["keys"] for d in doms], np.int64)
    share = np.array([d["share"] for d in doms], np.float64)
    contiguous_dom = np.array([d["contiguous"] for d in doms], bool)
    dom = rng.choice(len(doms), size=n, p=share / share.sum())
    per_key = rng.choice(np.asarray(params["per_key"], np.int64), size=n)
    span = -(-rows // per_key)
    start = (rng.random(n) * np.maximum(sizes[dom] - span, 1)).astype(
        np.int64)
    total = int(rows.sum())
    dom_rows = np.repeat(dom, rows)
    within = np.arange(total) - np.repeat(np.cumsum(rows) - rows, rows)
    ranged = np.repeat(start, rows) + within // np.repeat(per_key, rows)
    drawn = (rng.random(total) * sizes[dom_rows]).astype(np.int64)
    keys = (np.where(contiguous_dom[dom_rows], ranged, drawn)
            + (dom_rows.astype(np.int64) << DOMAIN_SHIFT))
    lo, hi = params["level_decades"]
    level = np.repeat(10.0 ** rng.uniform(lo, hi, n), rows)
    s_lo, s_hi = params["spread"]
    spread = np.repeat(rng.uniform(s_lo, s_hi, n), rows)
    values = level * (1.0 + spread * rng.normal(size=total))
    return Tables(names=[f"{prefix}{i:07d}" for i in range(n)],
                  starts=np.concatenate([[0], np.cumsum(rows)]),
                  keys=keys, values=values)


def query_rows(params, qparams, rng, n: int) -> np.ndarray:
    return heavy_tail(rng, n, qparams["rows_min"], qparams["rows_cap"],
                      qparams["rows_alpha"])


def queries(params, qparams, rng, rows: np.ndarray):
    doms = _domains(params)
    allowed = [_domain_index(params, name) for name in qparams["domains"]]
    lo, hi = qparams["level_decades"]
    out, signals = [], []
    for r in rows:
        d = allowed[int(rng.integers(len(allowed)))]
        size = doms[d]["keys"]
        r = int(min(r, size))
        k0 = int(rng.integers(0, size - r + 1))
        keys = (np.int64(d) << DOMAIN_SHIFT) + k0 + np.arange(r,
                                                              dtype=np.int64)
        signal = rng.normal(size=r)
        out.append((keys, 10.0 ** rng.uniform(lo, hi)
                    * (1.0 + qparams["spread"] * signal)))
        signals.append(signal)
    return out, signals


def planted(params, qparams, query, signal, rng, tag: str) -> Tables:
    p = qparams["planted"]
    keys = query[0]
    names, ks, vs = [], [], []
    lo, hi = p["level_decades"]
    for j, (rep, slope) in enumerate(zip(p["reps"], p["slopes"])):
        k = np.tile(keys, rep)
        v = 10.0 ** rng.uniform(lo, hi) * (1.0 + qparams["spread"] * (
            slope * np.tile(signal, rep) + p["noise"] * rng.normal(
                size=k.size)))
        names.append(f"{tag}_{j}")
        ks.append(k)
        vs.append(v)
    return from_lists(names, ks, vs)


def warm_query(params, qparams, nnz: int):
    d = _domain_index(params, qparams["domains"][-1])
    keys = (np.int64(d) << DOMAIN_SHIFT) + np.arange(nnz, dtype=np.int64)
    return keys, 1000.0 + np.arange(nnz, dtype=np.float64) % 7.0
