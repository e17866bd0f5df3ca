#!/usr/bin/env python3
"""Find an open-loop query cell's knee (traffic kind ``open``): one set-up, then one window at each
rate in turn, each with its own distinct queries.

    python3 chipbench/sweep.py --workload w1_query_open --seed 5 \
        --seconds 10 --rates 100,150,200

Prints, per rate, the requests, p50/p99/max latency, the backlog at the
close (requests due but not answered), the calls and the share of the
window spent inside ``search_batch``.  The knee is the highest rate whose
backlog stays near zero; the cell's traffic file then fixes a rate of about
four fifths of it.  Needs the chip, as ``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from chipbench import mix  # noqa: E402
from chipbench import run as bench_run  # noqa: E402


def sweep(cell, seed: int, seconds: float, rates, *, require_chip=True,
          cache=True):
    """One set-up, then a window at each rate: yields one row per rate."""
    if require_chip:
        bench_run.require_chips(cell.chips)
    if cache:
        bench_run.enable_compile_cache()
    from repro.serve import SketchSearchService

    traffic = mix.load(cell.traffic["kind"])
    counts = [int(np.ceil(r * seconds * 1.3)) + 16 for r in rates]
    inputs = traffic.build(cell.config, cell.traffic, seed, seconds,
                           n=sum(counts))
    svc = SketchSearchService(**cell.config["service"])
    bench_run.ingest_lake(svc, inputs.lake, cell.serving["ingest_batch"])
    traffic.warm_up(cell, svc, inputs, SketchSearchService)
    rng = np.random.default_rng([seed, 7])
    lo = 0
    for rate, n in zip(rates, counts):
        arr = np.cumsum(rng.exponential(1.0 / rate, size=n))
        arr = arr[arr < seconds]
        sub = mix.Inputs(lake=inputs.lake,
                         queries=inputs.queries[lo:lo + arr.size],
                         planted=inputs.planted[lo:lo + arr.size],
                         arrivals=arr)
        lo += n
        tracer = bench_run.Tracer(False, seconds, ".")
        win = traffic.window(cell, svc, sub, seconds, tracer)
        lat = win.latency_s[np.isfinite(win.latency_s)] * 1e3
        yield {"rate_per_s": rate, "requests": int(arr.size),
               "p50_ms": float(np.percentile(lat, 50)),
               "p99_ms": float(np.percentile(lat, 99)),
               "max_ms": float(lat.max()),
               "in_service_share": win.notes["in_service_s"] / seconds,
               **{k: win.notes[k] for k in ("backlog_at_close", "calls",
                                            "generator_lag_max_ms")}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    cell = bench_run.load_cell(args.workload)
    try:
        for row in sweep(cell, args.seed, args.seconds, rates):
            print(json.dumps(row), flush=True)
    except bench_run.NoChip as e:
        print(f"sweep.py: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
