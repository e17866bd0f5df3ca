"""Benchmark driver: one module per paper table/figure + theory + perf.

Prints ``name,us_per_call,derived,platform`` CSV rows (see
benchmarks.common.emit); every row names the platform it ran on.
``--fast`` shrinks trial counts for CI; the default sizes reproduce the
paper's qualitative results.  ``--json PATH`` additionally dumps every
emitted row as a JSON artifact (CI uploads ``BENCH_sketch.json`` from the
perf suite's smoke run).
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig4,fig5,fig6,table7,theory,perf")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write emitted rows as a JSON benchmark artifact")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (common, fig4_synthetic, fig5_worldbank, fig6_newsgroups,
                   perf_sketch, table7_overlap, theory_check)
    suites = {
        "fig4": fig4_synthetic.run,
        "fig5": fig5_worldbank.run,
        "fig6": fig6_newsgroups.run,
        "table7": table7_overlap.run,
        "theory": theory_check.run,
        "perf": perf_sketch.run,
    }
    only = ([s.strip() for s in args.only.split(",") if s.strip()]
            if args.only is not None else list(suites))
    # validate up front: a typo'd suite name must fail with a clear error
    # before any suite runs, not as a bare KeyError mid-run after the
    # header row is printed
    unknown = [s for s in only if s not in suites]
    if unknown:
        ap.error(f"unknown suite(s) for --only: {', '.join(unknown)} "
                 f"(choose from: {', '.join(suites)})")
    if not only:
        ap.error(f"--only selected no suites (choose from: {', '.join(suites)})")
    print("name,us_per_call,derived,platform")
    t0 = time.time()
    durations = {}
    failures = {}
    for name in only:
        t = time.time()
        # A suite's gated assertion (accuracy/monotonicity checks) must not
        # abort the run before later suites execute and the JSON artifact is
        # written -- CI uploads the artifact from failed runs too.  Record
        # the failure, keep going, and propagate a nonzero exit at the end.
        try:
            suites[name](fast=args.fast)
        except AssertionError as e:
            failures[name] = f"{type(e).__name__}: {e}"
            print(f"# {name} FAILED: {failures[name]}", flush=True)
        durations[name] = time.time() - t
        print(f"# {name} done in {durations[name]:.1f}s", flush=True)
    print(f"# total {time.time()-t0:.1f}s")
    # with REPRO_OBS=1 the run doubles as a telemetry capture: export the
    # metrics snapshot + Chrome trace next to the JSON artifact (dir from
    # REPRO_OBS_DIR, default obs_snapshot/).  Written even when suites
    # failed -- the trace of a failed run is the one worth reading.
    from repro import obs
    if obs.enabled():
        paths = obs.export_snapshot()
        for kind, path in sorted(paths.items()):
            print(f"# obs {kind}: {path}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"fast": bool(args.fast), "suites": only,
                       "suite_seconds": durations, "failures": failures,
                       "rows": common.RECORDS}, f, indent=2)
        print(f"# wrote {len(common.RECORDS)} rows to {args.json}")
    if failures:
        print(f"# {len(failures)} suite(s) failed: "
              f"{', '.join(sorted(failures))}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
