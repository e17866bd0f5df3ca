#!/usr/bin/env python3
"""The control's readings of a cell without the chip: the exact reference
computed in bfloat16, over the requests ``run.py --control`` would answer.

    python3 chipbench/control_readings.py --workload wdi_jl_query_open \
        --seconds 51 --seeds 1,2,3 --workers 6

Per seed it prints one JSON line with two sets of readings, each under the
cell's limits (:mod:`chipbench.check`):

* ``control``: what ``run.py --control`` reads.  The first
  ``control_requests`` requests with planted tables are ranked over the
  whole lake by the reference in bfloat16 and compared with the float64
  reference.  :class:`TableIndex` ranks as ``reference.LakeIndex.rank``
  does, bit for bit, in less time and memory;
* ``planted_bf16``: the same requests' planted tables answered by the
  reference in bfloat16 (``reference.join_stats``), whether or not the
  bfloat16 ranking finds them.  Their ``join_err``, ``sum_err`` and
  ``corr_err`` are what bfloat16 arithmetic does to the estimates alone.

It assumes that the window answered every request it was due, which a run
with no failed request does: the requests are then the seed's alone.  The
rankings run in ``--workers`` forked processes that share the lake's
index.  It needs no chip and no JAX.
"""
from __future__ import annotations

import argparse
import json
import multiprocessing
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from chipbench import check, mix, reference  # noqa: E402
from chipbench import run as bench_run  # noqa: E402

_INDEX = None          # the lake's index, shared with forked workers


class TableIndex:
    """The lake aggregated by (table, key), sorted by table, then key.

    :meth:`rank` hands ``reference._segment_stats`` the entries that
    ``reference.LakeIndex.rank`` hands it, in the same order (by table,
    then by key) and with the same sums (rows added in the lake's order),
    so its answer is LakeIndex's bit for bit.  It finds them with one
    lookup over the lake's entries in place of LakeIndex's gather and
    stable sort, a ``block`` of tables at a time: a table's statistics
    depend on its own entries alone."""

    def __init__(self, tables, block: int = 1024):
        self.n_tables, self.block = len(tables), block
        self.names = tables.names
        tid = np.repeat(np.arange(self.n_tables, dtype=np.int64),
                        tables.rows())
        self.domain, kid = np.unique(np.asarray(tables.keys, np.int64),
                                     return_inverse=True)
        cu, inv, cnt = np.unique((tid << 32) | kid.ravel(),
                                 return_inverse=True, return_counts=True)
        del tid, kid
        self.tid = cu >> 32
        self.kid = (cu & 0xFFFFFFFF).astype(np.int32)
        self.mult = cnt.astype(np.float64)
        self.vsum = np.bincount(inv.ravel(), weights=tables.values,
                                minlength=cu.size)
        self.start = np.searchsorted(self.tid, np.arange(self.n_tables + 1))

    def rank(self, query, top_k: int, min_join: float, dtype=np.float64):
        """As ``reference.LakeIndex.rank``."""
        uq, qm, qv = reference.aggregate(*query)
        at = np.minimum(np.searchsorted(self.domain, uq),
                        self.domain.size - 1)
        found = self.domain[at] == uq
        slot = np.full(self.domain.size, -1, np.int32)
        slot[at[found]] = np.flatnonzero(found)
        T = self.n_tables
        join, sum_b, corr = np.zeros(T), np.zeros(T), np.zeros(T)
        for lo in range(0, T, self.block):
            hi = min(lo + self.block, T)
            e = slice(self.start[lo], self.start[hi])
            pos = slot[self.kid[e]]
            idx = np.flatnonzero(pos >= 0)
            pos = pos[idx]
            j, s, c, _ = reference._segment_stats(
                self.tid[e][idx], qm[pos], qv[pos], self.mult[e][idx],
                self.vsum[e][idx], T, dtype)
            join[lo:hi], sum_b[lo:hi], corr[lo:hi] = j[lo:hi], s[lo:hi], \
                c[lo:hi]
        ok = np.flatnonzero(join >= min_join)
        best = ok[np.argsort(-np.abs(corr[ok]), kind="stable")[:top_k]]
        return [(self.names[i], float(join[i]), float(sum_b[i]),
                 float(corr[i])) for i in best]


def _rank(args):
    query, top_k, min_join = args
    return _INDEX.rank(query, top_k, min_join, dtype=reference.BF16)


def planted_bf16(queries, planted, lookup) -> list:
    """Each request's planted tables answered by the reference in
    bfloat16, as served answers."""
    out = []
    for query, names in zip(queries, planted):
        st = reference.join_stats(query, [lookup(n) for n in names],
                                  dtype=reference.BF16)
        out.append([(n, float(st.join[i]), float(st.sum_b[i]),
                     float(st.corr[i])) for i, n in enumerate(names)])
    return out


def readings(cell, seed: int, seconds: float, workers: int = 1) -> dict:
    """Both sets of readings of one seed (see the module's docstring)."""
    global _INDEX
    t0 = time.time()
    s, limits = cell.serving, cell.spec["limits"]
    traffic = mix.load(cell.traffic["kind"])
    inputs = traffic.build(cell.config, cell.traffic, seed, seconds)
    n = len(inputs.queries)
    win = mix.Window(attempted=n, failed=0, answers=[[]] * n)
    have = [i for i, p in enumerate(inputs.planted) if p]
    pick = have[:int(cell.spec["control_requests"])]
    queries = [inputs.queries[i] for i in pick]
    planted = [inputs.planted[i] for i in pick]
    n_planted = sum(len(p) for p in planted)
    built = time.time()
    _INDEX = TableIndex(traffic.lake_at_close(cell, inputs, win))
    indexed = time.time()
    jobs = [(q, s["top_k"], s["min_join"]) for q in queries]
    if workers > 1:
        with multiprocessing.get_context("fork").Pool(workers) as pool:
            ranked = pool.map(_rank, jobs, chunksize=1)
    else:
        ranked = [_rank(j) for j in jobs]
    _INDEX = None
    ranked_at = time.time()
    out = {"seed": seed, "requests": len(pick), "planted": n_planted,
           "seconds": {"inputs": built - t0, "index": indexed - built,
                       "rank": ranked_at - indexed}}
    for name, answers in (("control", ranked),
                          ("planted_bf16", planted_bf16(
                              queries, planted, inputs.lookup))):
        r = check.compare(queries, answers, planted, inputs.lookup)
        out[name] = {"correct": check.verdict(r, limits, 0, n_planted),
                     "checks": check.lines(r, limits)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one line each")
    ap.add_argument("--workers", type=int, default=1)
    args = ap.parse_args(argv)
    cell = bench_run.load_cell(args.workload)
    for seed in (int(x) for x in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds, args.workers)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
