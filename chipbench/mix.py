"""What every traffic kind shares, and the loader that finds a kind by name.

A traffic file (``chipbench/traffic/<traffic>.json``) names its ``kind``;
the kind is a module of its own, ``chipbench/traffic/<kind>.py``, found by
that name, so a new arrival process or a closed loop is a new file.  A kind
module exposes

* ``build(cfg, traffic, seed, seconds) -> Inputs``: the lake to ingest at
  set-up and the work of the window, from the seed;
* ``warm_up(cell, svc, inputs, service_cls)``: run every shape the window
  can meet, and only those;
* ``window(cell, svc, inputs, seconds, tracer) -> Window``: the measured
  window;
* ``checked(cell, inputs, win, svc) -> (queries, answers, planted)``: the
  requests the comparison holds to the reference;
* ``lake_at_close(cell, inputs, win) -> Tables``: what the lake holds once
  the window has closed (the control ranks it);
* ``readings(win) -> {metric: value}``: the kind's end-to-end readings.

Sizes (table and query row counts) and arrival gaps come from a stream
seeded by the traffic file's ``shape_seed``; ``--seed`` permutes them and
draws everything else.  So every seed of a cell does the same amount of
work, in another order and on other data.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import pathlib
from typing import List, Optional

import numpy as np

from chipbench.lakes import Tables

TRAFFIC = pathlib.Path(__file__).resolve().parent / "traffic"
GRACE_S = 60.0                 # wait for due answers past the close

# independent streams of one seed
SHAPE, ORDER, LAKE, QUERY, PLANT, POOL = range(6)


def rng(seed: int, stream: int):
    return np.random.default_rng([int(seed) % (1 << 64), stream])


@dataclasses.dataclass
class Inputs:
    lake: Tables                       # ingested at set-up, in this order
    queries: List[tuple] = dataclasses.field(default_factory=list)
    planted: List[List[str]] = dataclasses.field(default_factory=list)
    arrivals: Optional[np.ndarray] = None   # s after window start, sorted
    pool: Optional[Tables] = None      # fresh tables, in order
    warm_nnz: List[int] = dataclasses.field(default_factory=list)

    def lookup(self, name: str):
        for t in (self.lake, self.pool):
            if t is not None:
                try:
                    return t.table(t.index(name))[1:]
                except KeyError:
                    pass
        return None


@dataclasses.dataclass
class Window:
    attempted: int
    failed: int
    answers: list                 # per request: [(name, join, sum, corr)]
    latency_s: Optional[np.ndarray] = None   # due -> returned, nan if never
    done_s: Optional[np.ndarray] = None      # returned, s after the start
    rows_per_s: float = 0.0
    batches_acked: int = 0
    notes: dict = dataclasses.field(default_factory=dict)


def answer(res) -> list:
    """A served result list as ``[(name, join, sum_b, corr)]``."""
    return [(r.name, float(r.join_size), float(r.sum_b), float(r.corr))
            for r in res]


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def planted_tables(kind, lp, qp, queries, signals, which, seed, prefix):
    r = rng(seed, PLANT)
    parts, names = [], [[] for _ in queries]
    for q in which:
        t = kind.planted(lp, qp, queries[q], signals[q], r,
                         f"{prefix}{q:06d}")
        parts.append(t)
        names[q] = list(t.names)
    return parts, names


def by_size(t: Tables) -> Tables:
    """Set-up ingests in size order: a batch pads to its longest table."""
    return t.take(np.argsort(t.rows(), kind="stable"))


def widths(lo: int, hi: int, step: int = 128) -> List[int]:
    return sorted(set(range(lo, hi + 1, step)) | {hi})


def load(kind: str):
    """The traffic kind's module, ``chipbench/traffic/<kind>.py``."""
    path = TRAFFIC / f"{kind}.py"
    if not path.is_file():
        raise ValueError(f"unknown traffic kind {kind!r}: no {path.name} "
                         f"in {TRAFFIC}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_traffic_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build(cfg, traffic, seed: int, seconds: float) -> Inputs:
    return load(traffic["kind"]).build(cfg, traffic, seed, seconds)
