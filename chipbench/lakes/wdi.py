"""WDI: dense, high-overlap indicator panels (World Bank World Development
Indicators; the paper's §6 World Bank study).

A table is one indicator over a panel: a random 60-100% of the economies
times a contiguous 60-100% of the years, one row per (economy, year).  Its
level is log-uniform over six decades and its rows deviate from the level
by a heavy-tailed (Student t) draw, so panels carry outliers as Fig. 5's
kurtosis buckets do.  A query is a new panel of the same shape at a 5%
spread; its planted tables cover the query's panel with values at their own
level that follow the query's deviations.

A panel's size is coded as ``economies * 100 + years``: the ``rows``
arrays passed in and out of this module hold that code, so every seed of a
cell gets the same multiset of panel shapes.
"""
from __future__ import annotations

import numpy as np

from . import Tables, from_lists


def _shape(params):
    return int(params["economies"]), int(params["years"])


CODE = 100                     # size code: economies * CODE + years


def _panel_rows(params, rng, n: int) -> np.ndarray:
    E, Y = _shape(params)
    lo = params["cover_min"]
    ne = rng.integers(int(np.ceil(lo * E)), E + 1, size=n)
    ny = rng.integers(int(np.ceil(lo * Y)), Y + 1, size=n)
    return (ne * CODE + ny).astype(np.int64)


def background_rows(params, rng, n: int) -> np.ndarray:
    return _panel_rows(params, rng, n)


def query_rows(params, qparams, rng, n: int) -> np.ndarray:
    return _panel_rows(params, rng, n)


def _panel(params, rng, code: int) -> np.ndarray:
    """Keys of the panel of size ``code``: economies x contiguous years."""
    E, Y = _shape(params)
    ne, ny = code // CODE, code % CODE
    econ = np.sort(rng.choice(E, size=ne, replace=False)).astype(np.int64)
    y0 = int(rng.integers(0, Y - ny + 1))
    years = y0 + np.arange(ny, dtype=np.int64)
    return (econ[:, None] * Y + years[None, :]).ravel()


def _deviation(rng, df: float, size: int) -> np.ndarray:
    """Student-t deviations scaled to unit variance where it exists."""
    t = rng.standard_t(df, size=size)
    return t * np.sqrt((df - 2.0) / df) if df > 2 else t


def background(params, rng, rows: np.ndarray, prefix: str = "t") -> Tables:
    names, ks, vs = [], [], []
    lo, hi = params["level_decades"]
    s_lo, s_hi = params["spread"]
    dfs = np.asarray(params["tail_df"], np.float64)
    for i, r in enumerate(rows):
        keys = _panel(params, rng, int(r))
        dev = _deviation(rng, float(rng.choice(dfs)), keys.size)
        level = 10.0 ** rng.uniform(lo, hi)
        names.append(f"{prefix}{i:07d}")
        ks.append(keys)
        vs.append(level * (1.0 + rng.uniform(s_lo, s_hi) * dev))
    return from_lists(names, ks, vs)


def queries(params, qparams, rng, rows: np.ndarray):
    lo, hi = qparams["level_decades"]
    out, signals = [], []
    for r in rows:
        keys = _panel(params, rng, int(r))
        signal = _deviation(rng, qparams["tail_df"], keys.size)
        out.append((keys, 10.0 ** rng.uniform(lo, hi)
                    * (1.0 + qparams["spread"] * signal)))
        signals.append(signal)
    return out, signals


def planted(params, qparams, query, signal, rng, tag: str) -> Tables:
    p = qparams["planted"]
    keys = query[0]
    lo, hi = p["level_decades"]
    names, ks, vs = [], [], []
    for j, slope in enumerate(p["slopes"]):
        v = 10.0 ** rng.uniform(lo, hi) * (1.0 + qparams["spread"] * (
            slope * signal + p["noise"] * rng.normal(size=keys.size)))
        names.append(f"{tag}_{j}")
        ks.append(keys)
        vs.append(v)
    return from_lists(names, ks, vs)


def warm_query(params, qparams, nnz: int):
    keys = np.arange(nnz, dtype=np.int64)
    return keys, 1000.0 + np.arange(nnz, dtype=np.float64) % 7.0
