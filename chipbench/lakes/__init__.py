"""Lake generators, one module per deployment kind, found by name.

A configuration's ``lake.kind`` names a module here.  Each module exposes

* ``background(params, rng, rows) -> Tables``: lake tables with the given
  row counts (the counts come from the caller, so every seed of a cell
  ingests the same multiset of sizes);
* ``background_rows(params, rng, n) -> int64 [n]``: n table sizes drawn
  from the deployment's size distribution (a row count, or a module's own
  code for a table's shape);
* ``query_rows(params, qparams, rng, n) -> int64 [n]``: sizes of query
  tables;
* ``queries(params, qparams, rng, rows) -> (list[(keys, values)], signals)``;
* ``planted(params, qparams, query, signal, rng, tag) -> Tables``: the
  joinable, correlated tables planted for one query;
* ``warm_query(params, qparams, nnz) -> (keys, values)``: a query table
  with exactly ``nnz`` distinct keys.

Keys are int64 below 2^31, values float64.  Nothing here imports the
program under test.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Tables:
    """Named (key, value) tables as slices of two flat arrays."""
    names: List[str]
    starts: np.ndarray             # int64 [T + 1] row offsets
    keys: np.ndarray               # int64 [rows]
    values: np.ndarray             # float64 [rows]
    _where: Optional[Dict[str, int]] = dataclasses.field(default=None,
                                                         repr=False)

    def __len__(self) -> int:
        return len(self.names)

    def table(self, i: int):
        lo, hi = self.starts[i], self.starts[i + 1]
        return self.names[i], self.keys[lo:hi], self.values[lo:hi]

    def rows(self) -> np.ndarray:
        return np.diff(self.starts)

    def index(self, name: str) -> int:
        if self._where is None:
            self._where = {n: i for i, n in enumerate(self.names)}
        return self._where[name]

    def take(self, order) -> "Tables":
        """The tables at positions ``order``, in that order."""
        order = np.asarray(order, np.int64)
        lens = self.rows()[order]
        lo = self.starts[order]
        idx = (np.repeat(lo - np.concatenate([[0], np.cumsum(lens)[:-1]]),
                         lens) + np.arange(int(lens.sum())))
        return Tables(names=[self.names[i] for i in order],
                      starts=np.concatenate([[0], np.cumsum(lens)]),
                      keys=self.keys[idx], values=self.values[idx])

    def batch(self, lo: int, hi: int):
        """Tables ``lo:hi`` as the ``(name, keys, values)`` list that
        ``ingest_many_sharded`` takes."""
        return [self.table(i) for i in range(lo, min(hi, len(self)))]


def concat(parts: List[Tables]) -> Tables:
    parts = [p for p in parts if len(p)]
    if not parts:
        return Tables([], np.zeros(1, np.int64), np.zeros(0, np.int64),
                      np.zeros(0))
    lens = np.concatenate([p.rows() for p in parts])
    return Tables(names=[n for p in parts for n in p.names],
                  starts=np.concatenate([[0], np.cumsum(lens)]),
                  keys=np.concatenate([p.keys for p in parts]),
                  values=np.concatenate([p.values for p in parts]))


def from_lists(names, keys, values) -> Tables:
    lens = np.array([len(k) for k in keys], np.int64)
    return Tables(names=list(names),
                  starts=np.concatenate([[0], np.cumsum(lens)]),
                  keys=np.concatenate(keys).astype(np.int64),
                  values=np.concatenate(values).astype(np.float64))


def heavy_tail(rng, n: int, lo: int, cap: int, alpha: float) -> np.ndarray:
    """Pareto(alpha) row counts from ``lo``, capped at ``cap``."""
    return np.minimum(np.floor(lo * rng.random(n) ** (-1.0 / alpha)),
                      cap).astype(np.int64)


def load(kind: str):
    return importlib.import_module(f"chipbench.lakes.{kind}")
