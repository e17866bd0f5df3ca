"""The plain exact reference: join size, post-join sum and correlation of a
query table with lake tables, from the raw (key, value) rows in numpy.

Semantics (the configurations state them as guarantees):

* a table is aggregated by key first: its multiplicity ``mult(k)`` (rows
  with key k) and its value sum ``vsum(k)``;
* ``join``  = sum_k mult_a(k) * mult_b(k), the joined row pairs (SQL join
  cardinality);
* ``sum_b`` = sum_k mult_a(k) * vsum_b(k), the sum of B's values over the
  joined row pairs;
* ``corr``  = Pearson's r over the shared keys of (vsum_a(k), vsum_b(k)),
  two-pass (centred), 0 where fewer than two keys are shared or a side is
  constant.

``join`` with the norms of the multiplicity vectors is what
``chip_smoke.exact_join`` computes; :func:`join_stats` does it for many
tables at once and adds the sum and the correlation.

``dtype`` is float64 for the reference.  The control computes the same
formulas in bfloat16 throughout: inputs, products and running sums are all
held in bfloat16 (``np.add.reduceat`` over bfloat16 arrays accumulates in
bfloat16, in order).

Nothing here imports the program under test.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16
KEY_BITS = 31


def aggregate(keys, values):
    """Distinct keys, their multiplicities and value sums (float64)."""
    u, inv, c = np.unique(np.asarray(keys, np.int64), return_inverse=True,
                          return_counts=True)
    vs = np.bincount(inv.ravel(), weights=np.asarray(values, np.float64),
                     minlength=u.size)
    return u, c.astype(np.float64), vs


@dataclasses.dataclass
class Stats:
    """Per-table join statistics against one query, ``[T]`` each."""
    join: np.ndarray
    sum_b: np.ndarray
    corr: np.ndarray
    shared: np.ndarray           # shared distinct keys
    norm_a: float                # ||mult_a||_2 (float64)
    norm_b: np.ndarray           # ||mult_b||_2 (float64)
    vnorm_b: np.ndarray          # ||vsum_b||_2 (float64)


def _segment_stats(tid, a_m, a_v, b_m, b_v, n_tables: int, dtype):
    """join, sum_b, corr, shared per table from matched (table, key)
    entries sorted by ``tid``; arithmetic in ``dtype``."""
    join = np.zeros(n_tables)
    sum_b = np.zeros(n_tables)
    corr = np.zeros(n_tables)
    shared = np.zeros(n_tables, np.int64)
    if tid.size == 0:
        return join, sum_b, corr, shared
    starts = np.flatnonzero(np.r_[True, tid[1:] != tid[:-1]])
    seg_tid = tid[starts]
    lens = np.diff(np.r_[starts, tid.size])
    a_m, a_v, b_m, b_v = (np.asarray(x, np.float64).astype(dtype)
                          for x in (a_m, a_v, b_m, b_v))

    def ssum(x):
        return np.add.reduceat(x, starts)

    n = ssum(np.ones(tid.size, dtype))
    ma = np.repeat(ssum(a_v) / n, lens)
    mb = np.repeat(ssum(b_v) / n, lens)
    da, db = a_v - ma, b_v - mb
    sxx, syy, sxy = ssum(da * da), ssum(db * db), ssum(da * db)
    den = sxx * syy
    ok = (lens >= 2) & (np.asarray(den, np.float64) > 0)
    r = sxy / np.sqrt(np.where(ok, den, np.ones_like(den)))
    join[seg_tid] = np.asarray(ssum(a_m * b_m), np.float64)
    sum_b[seg_tid] = np.asarray(ssum(a_m * b_v), np.float64)
    corr[seg_tid] = np.where(ok, np.clip(np.asarray(r, np.float64), -1, 1),
                             0.0)
    shared[seg_tid] = lens
    return join, sum_b, corr, shared


def join_stats(query, tables, dtype=np.float64) -> Stats:
    """Statistics of ``query = (keys, values)`` against each of ``tables``,
    a list of ``(keys, values)``."""
    uq, qm, qv = aggregate(*query)
    T = len(tables)
    lens = np.array([len(k) for k, _ in tables], np.int64)
    if T == 0 or lens.sum() == 0:
        z = np.zeros(T)
        return Stats(z, z, z, np.zeros(T, np.int64),
                     float(np.linalg.norm(qm)), z, z)
    tid = np.repeat(np.arange(T, dtype=np.int64), lens)
    keys = np.concatenate([np.asarray(k, np.int64) for k, _ in tables])
    vals = np.concatenate([np.asarray(v, np.float64) for _, v in tables])
    c = (tid << KEY_BITS) | keys
    cu, inv, cnt = np.unique(c, return_inverse=True, return_counts=True)
    b_m = cnt.astype(np.float64)
    b_v = np.bincount(inv.ravel(), weights=vals, minlength=cu.size)
    t_u, k_u = cu >> KEY_BITS, cu & ((1 << KEY_BITS) - 1)
    norm_b = np.sqrt(np.bincount(t_u, weights=b_m * b_m, minlength=T))
    vnorm_b = np.sqrt(np.bincount(t_u, weights=b_v * b_v, minlength=T))
    pos = np.minimum(np.searchsorted(uq, k_u), uq.size - 1)
    hit = uq[pos] == k_u
    join, sum_b, corr, shared = _segment_stats(
        t_u[hit], qm[pos[hit]], qv[pos[hit]], b_m[hit], b_v[hit], T, dtype)
    return Stats(join, sum_b, corr, shared, float(np.linalg.norm(qm)),
                 norm_b, vnorm_b)


class LakeIndex:
    """The whole lake aggregated by (key, table), sorted by key, so that a
    query's join with every table is one gather.  Used to rank the lake the
    way a full exact search would (the control's answers)."""

    def __init__(self, tables):
        self.tables = tables
        T = len(tables)
        tid = np.repeat(np.arange(T, dtype=np.int64), tables.rows())
        c = (np.asarray(tables.keys, np.int64) << 32) | tid
        cu, inv, cnt = np.unique(c, return_inverse=True, return_counts=True)
        self.key = cu >> 32
        self.tid = cu & ((1 << 32) - 1)
        self.mult = cnt.astype(np.float64)
        self.vsum = np.bincount(inv.ravel(), weights=tables.values,
                                minlength=cu.size)

    def rank(self, query, top_k: int, min_join: float, dtype=np.float64):
        """The best ``top_k`` tables by |corr| among those whose join is at
        least ``min_join``: ``[(name, join, sum_b, corr)]``."""
        uq, qm, qv = aggregate(*query)
        lo = np.searchsorted(self.key, uq, "left")
        hi = np.searchsorted(self.key, uq, "right")
        n = hi - lo
        q_of = np.repeat(np.arange(uq.size), n)
        idx = (np.repeat(lo - np.r_[0, np.cumsum(n)[:-1]], n)
               + np.arange(int(n.sum())))
        tid = self.tid[idx]
        order = np.argsort(tid, kind="stable")
        tid, q_of, idx = tid[order], q_of[order], idx[order]
        T = len(self.tables)
        join, sum_b, corr, _ = _segment_stats(
            tid, qm[q_of], qv[q_of], self.mult[idx], self.vsum[idx], T,
            dtype)
        ok = np.flatnonzero(join >= min_join)
        best = ok[np.argsort(-np.abs(corr[ok]), kind="stable")[:top_k]]
        return [(self.tables.names[i], float(join[i]), float(sum_b[i]),
                 float(corr[i])) for i in best]
