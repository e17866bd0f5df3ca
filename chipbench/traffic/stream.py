"""A crawler: fresh tables ingested back to back.

Traffic file keys: ``base_tables`` (the lake ingested at set-up),
``pool_tables`` (fresh tables the window may ingest), ``shape_seed`` and
``queries`` (the lake kind's query parameters, for the check queries).

The window ingests the pool in batches of the configuration's
``ingest_batch``, in discovery order, and hands the next batch once the
last is acknowledged.  Each batch holds the planted twins of one check
query, so the check reads back through ``search_batch`` what the window
wrote.
"""
from __future__ import annotations

import gc
import sys
import time
from typing import List

import numpy as np

from chipbench import lakes, mix
from chipbench.lakes import Tables, concat


def build(cfg, traffic, seed: int, seconds: float) -> mix.Inputs:
    lp, qp = cfg["lake"], traffic["queries"]
    kind = lakes.load(lp["kind"])
    B = cfg["serving"]["ingest_batch"]
    n_batches = traffic["pool_tables"] // B
    per_query = len(qp["planted"]["slopes"])
    shape = mix.rng(traffic["shape_seed"], mix.SHAPE)
    base_sizes = kind.background_rows(lp, shape, traffic["base_tables"])
    pool_sizes = kind.background_rows(lp, shape,
                                      n_batches * (B - per_query))
    check_sizes = kind.query_rows(lp, qp, shape, n_batches)

    order = mix.rng(seed, mix.ORDER)
    base = kind.background(lp, mix.rng(seed, mix.LAKE),
                           base_sizes[order.permutation(base_sizes.size)],
                           prefix="b")
    fresh = kind.background(lp, mix.rng(seed, mix.POOL),
                            pool_sizes[order.permutation(pool_sizes.size)],
                            prefix="f")
    queries, signals = kind.queries(lp, qp, mix.rng(seed, mix.QUERY),
                                    check_sizes[order.permutation(n_batches)])
    twins, planted = mix.planted_tables(kind, lp, qp, queries, signals,
                                        range(n_batches), seed, "w")
    # batch b: its share of fresh tables with query b's twins at random
    # positions among them
    parts, per_bg = [], B - per_query
    for b in range(n_batches):
        batch = concat([fresh.take(np.arange(b * per_bg, (b + 1) * per_bg)),
                        twins[b]])
        parts.append(batch.take(order.permutation(len(batch))))
    pool = concat(parts)
    return mix.Inputs(lake=mix.by_size(base), queries=queries,
                      planted=planted, pool=pool,
                      warm_nnz=pool_widths(pool, B))


def pool_widths(pool: Tables, batch: int) -> List[int]:
    """Largest distinct-key count of each ingest batch of the pool, rounded
    up to 128: the pad widths the window's sketch launches can meet."""
    T = len(pool)
    tid = np.repeat(np.arange(T, dtype=np.int64), pool.rows())
    cu = np.unique((tid << 31) | pool.keys)
    distinct = np.bincount(cu >> 31, minlength=T)
    out = set()
    for lo in range(0, T, batch):
        top = int(distinct[lo:lo + batch].max())
        out.add(-(-top // 128) * 128)
    return sorted(out)


def warm_up(cell, svc, inputs: mix.Inputs, service_cls) -> None:
    """The sketch launch of a batch pads to its widest table: a throwaway
    service compiles each width the pool's batches reach."""
    lp, qp = cell.config["lake"], cell.traffic["queries"]
    kind = lakes.load(lp["kind"])
    B = cell.serving["ingest_batch"]
    for nnz in inputs.warm_nnz:
        keys, vals = kind.warm_query(lp, qp, nnz)
        batch = [("warm_0", keys, vals)] + [
            (f"warm_{i}", np.array([i], np.int64), np.array([1.0]))
            for i in range(1, B)]
        service_cls(**cell.config["service"]).ingest_many_sharded(
            batch, shards=1)
        gc.collect()


def window(cell, svc, inputs: mix.Inputs, seconds: float,
           tracer) -> mix.Window:
    """The batch under way at the close completes and counts, with its
    time."""
    B = cell.serving["ingest_batch"]
    pool = inputs.pool
    rows = attempted = failed = b = 0
    t0 = time.perf_counter()
    while b * B < len(pool):
        now = time.perf_counter() - t0
        tracer.poll(now)
        if now >= seconds:
            break
        lo, hi = b * B, min((b + 1) * B, len(pool))
        attempted += hi - lo
        try:
            with mix.annotate("bench.ingest_batch"):
                svc.ingest_many_sharded(pool.batch(lo, hi), shards=1)
        except Exception as e:
            print(f"ingest_many_sharded raised {e!r}", file=sys.stderr)
            failed += hi - lo
            break
        rows += int(pool.starts[hi] - pool.starts[lo])
        b += 1
    elapsed = time.perf_counter() - t0
    tracer.finish()
    return mix.Window(attempted=attempted, failed=failed, answers=[],
                      rows_per_s=rows / elapsed, batches_acked=b,
                      notes={"tables_acked": b * B, "elapsed_s": elapsed,
                             "pool_exhausted": b * B >= len(pool)})


def checked(cell, inputs: mix.Inputs, win: mix.Window, svc) -> tuple:
    """The check queries of the batches the window acknowledged, read back
    through the service."""
    s = cell.serving
    qs = inputs.queries[:win.batches_acked]
    out = svc.search_batch(qs, top_k=s["top_k"], min_join=s["min_join"],
                           micro_batch=s["micro_batch"]) if qs else []
    return qs, [mix.answer(r) for r in out], \
        inputs.planted[:win.batches_acked]


def lake_at_close(cell, inputs: mix.Inputs, win: mix.Window) -> Tables:
    acked = win.batches_acked * cell.serving["ingest_batch"]
    return concat([inputs.lake, inputs.pool.take(np.arange(acked))])


def readings(win: mix.Window) -> dict:
    """Rows of all tables acknowledged in the window over its seconds."""
    return {"ingest_rows_per_s": win.rows_per_s}
