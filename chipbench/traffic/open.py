"""Open loop: independent requests at a fixed rate.

Traffic file keys: ``rate_per_s`` (requests a second), ``planted_share``
(share of requests with joinable, correlated tables planted in the lake),
``shape_seed``, ``warm_nnz`` (the least and the most distinct keys of a
query) and ``queries`` (the lake kind's query parameters).

``rate_per_s`` times the window's seconds requests, each a distinct query
table; arrival gaps are exponential (Poisson arrivals).  One host thread
hands the oldest due requests, at most the configuration's
``micro_batch``, to one ``search_batch`` call; when none is due it waits
for the next arrival.  Each request is timed from when it was due to when
its call returned.
"""
from __future__ import annotations

import sys
import time
from typing import Optional

import numpy as np

from chipbench import lakes, mix
from chipbench.lakes import concat


def build(cfg, traffic, seed: int, seconds: float,
          n: Optional[int] = None) -> mix.Inputs:
    lp, qp = cfg["lake"], traffic["queries"]
    kind = lakes.load(lp["kind"])
    n = int(round(traffic["rate_per_s"] * seconds)) if n is None else n
    shape = mix.rng(traffic["shape_seed"], mix.SHAPE)
    gaps = shape.exponential(size=n + 1)
    sizes = kind.query_rows(lp, qp, shape, n)
    n_planted = int(round(traffic["planted_share"] * n))
    per_query = len(qp["planted"]["slopes"])
    bg_sizes = kind.background_rows(lp, shape, cfg["tables"]
                                    - n_planted * per_query)

    flagged = np.zeros(n, bool)
    flagged[shape.choice(n, size=n_planted, replace=False)] = True

    order = mix.rng(seed, mix.ORDER)
    gaps = gaps[order.permutation(n + 1)]
    arrivals = seconds * np.cumsum(gaps)[:-1] / gaps.sum()
    perm = order.permutation(n)
    sizes, which = sizes[perm], np.flatnonzero(flagged[perm])

    queries, signals = kind.queries(lp, qp, mix.rng(seed, mix.QUERY), sizes)
    parts, planted = mix.planted_tables(kind, lp, qp, queries, signals,
                                        which, seed, "p")
    bg = kind.background(lp, mix.rng(seed, mix.LAKE),
                         bg_sizes[order.permutation(bg_sizes.size)])
    lo, hi = traffic["warm_nnz"]
    return mix.Inputs(lake=mix.by_size(concat([bg] + parts)),
                      queries=queries, planted=planted, arrivals=arrivals,
                      warm_nnz=mix.widths(lo, hi))


def warm_up(cell, svc, inputs: mix.Inputs, service_cls) -> None:
    """One micro-batch at every query width from the least to the most."""
    lp, qp = cell.config["lake"], cell.traffic["queries"]
    kind = lakes.load(lp["kind"])
    s = cell.serving
    for nnz in inputs.warm_nnz:
        svc.search_batch([kind.warm_query(lp, qp, nnz)],
                         top_k=s["top_k"], min_join=s["min_join"],
                         micro_batch=s["micro_batch"])


def window(cell, svc, inputs: mix.Inputs, seconds: float,
           tracer) -> mix.Window:
    s = cell.serving
    arr = inputs.arrivals
    n = arr.size
    done = np.full(n, np.nan)
    answers: list = [None] * n
    lags, errors, calls, in_service = [], 0, 0, 0.0
    t0 = time.perf_counter()
    i = 0
    while i < n:
        now = time.perf_counter() - t0
        tracer.poll(now)
        if arr[i] > now:
            with mix.annotate("bench.await_arrival"):
                time.sleep(arr[i] - now)
            lags.append(time.perf_counter() - t0 - arr[i])
            continue
        if now > seconds + mix.GRACE_S:
            break
        j = i + 1
        while j < n and j - i < s["micro_batch"] and arr[j] <= now:
            j += 1
        try:
            with mix.annotate("bench.search_batch"):
                out = svc.search_batch(inputs.queries[i:j],
                                       top_k=s["top_k"],
                                       min_join=s["min_join"],
                                       micro_batch=s["micro_batch"])
        except Exception as e:          # counted as failed requests
            print(f"search_batch raised {e!r}", file=sys.stderr)
            errors += j - i
            out = None
        t = time.perf_counter() - t0
        calls += 1
        in_service += t - now
        if out is not None:
            done[i:j] = t
            for k, res in zip(range(i, j), out):
                answers[k] = mix.answer(res)
        i = j
    tracer.finish()
    latency = done - arr
    backlog = int(np.sum(~(done <= seconds)))   # due, not answered at close
    lag = np.asarray(lags) if lags else np.zeros(1)
    return mix.Window(attempted=n, failed=int(np.isnan(done).sum()),
                      answers=answers, latency_s=latency, done_s=done,
                      notes={"backlog_at_close": backlog, "calls": calls,
                             "in_service_s": in_service,
                             "generator_lag_p50_ms":
                                 float(np.median(lag) * 1e3),
                             "generator_lag_max_ms": float(lag.max() * 1e3),
                             "search_errors": errors})


def checked(cell, inputs: mix.Inputs, win: mix.Window, svc) -> tuple:
    """Every request the window answered."""
    done = [i for i, a in enumerate(win.answers) if a is not None]
    return ([inputs.queries[i] for i in done],
            [win.answers[i] for i in done],
            [inputs.planted[i] for i in done])


def lake_at_close(cell, inputs: mix.Inputs, win: mix.Window):
    return inputs.lake


def readings(win: mix.Window) -> dict:
    """The median over all requests of the window.  Their 99th percentile
    is per layer (``metrics/latency_p99_ms.py``): host stalls set it."""
    lat = win.latency_s[np.isfinite(win.latency_s)] * 1e3
    if not lat.size:
        return {}
    return {"query_p50_ms": float(np.percentile(lat, 50))}
