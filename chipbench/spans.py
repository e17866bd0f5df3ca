#!/usr/bin/env python3
"""The program's own spans in a traced run.

While obs is on, every ``repro.obs`` span is also a profiler annotation of
its plain name (``serve.*``, ``query.*``, ``ops.*``, ``merge.*``,
``store.*``), on the profiler's host clock, the one the device planes
share; and it is an event of the obs ring, which a reader gets as
``ctx.spans`` (``ts``/``dur`` in us, ``id`` and ``parent`` ids).

* :func:`ms_per_batch` reads the ring: a child span of
  ``serve.search_batch`` per call (``metrics/query_prep_ms_per_batch.py``
  and its twins);
* :func:`program_spans` reads the annotations out of an ``.xplane.pb``;
  :func:`span_per_call` is :meth:`tracing.Trace.per_call`'s twin for them,
  and :func:`idle_by_span` splits :meth:`tracing.Trace.idle_by_label`'s
  gaps further by the innermost program span open on the host.

Run as a script it makes one traced run of a cell, as ``run.py --trace 1``
does, and keeps the program's spans from the profiler trace as well:

    python3 chipbench/spans.py --workload w1_query_open --seed <n> \
        --seconds 51 [--fixture <path.json.gz>]

It prints the run's result line with ``spans`` added: the idle gaps split
by span, each span's ms per ``bench.search_batch`` call, the bytes and
rate of ``query.fetch``, and every ``serve.search_batch`` over 150 ms with
what its children took.  ``--fixture`` writes the reduced trace with the
program spans and the ring (:func:`save`).
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import json
import pathlib
import sys
from typing import Dict, Iterator, List, Optional, Tuple

if __name__ == "__main__":
    _ROOT = pathlib.Path(__file__).resolve().parents[1]
    for _p in (str(_ROOT / "src"), str(_ROOT)):
        if _p not in sys.path:
            sys.path.insert(0, _p)

from chipbench import tracing  # noqa: E402

PREFIXES = ("serve.", "query.", "ops.", "merge.", "store.")
OUTER = "serve.search_batch"
SLOW_MS = 150.0


# -- the ring (ctx.spans) ----------------------------------------------------
def ms_per_batch(events: List[dict], name: str,
                 outer: str = OUTER) -> Optional[float]:
    """Milliseconds of the ``name`` spans whose parent is an ``outer`` span,
    per ``outer`` span; ``None`` where no such child ran."""
    calls = {e["id"] for e in events if e["name"] == outer and "id" in e}
    inner = [e["dur"] for e in events
             if e["name"] == name and e.get("parent") in calls]
    if not inner:
        return None
    return sum(inner) / 1e3 / len(calls)


# -- the profiler trace ------------------------------------------------------
def program_spans(path: str) -> List[list]:
    """The program's spans in one ``.xplane.pb``: ``[name, start, dur]``
    in ns on the host clock, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend([e.name, e.start_ns, e.duration_ns]
                           for e in line.events
                           if e.name.startswith(PREFIXES))
    return sorted(out, key=lambda s: s[1])


def span_per_call(trace: tracing.Trace, program: List[list], outer: str,
                  name: str) -> Tuple[float, int]:
    """Seconds of the ``name`` spans that start inside the ``outer``
    annotations lying wholly in the window, and the number of those
    annotations."""
    t0, t1 = trace.window()
    calls = [(s, s + d) for n, s, d in trace.host
             if n == outer and s >= t0 and s + d <= t1]
    tot = sum(d for n, s, d in program
              if n == name and any(a <= s < b for a, b in calls))
    return tot * 1e-9, len(calls)


def _innermost(spans: List[tuple], starts: List[float], longest: float,
               lo: float, hi: float, default: str
               ) -> Iterator[Tuple[str, float]]:
    """``[lo, hi)`` cut where a program span opens or closes, each piece
    named by the innermost span covering it (the latest to open), else
    ``default``."""
    first = bisect.bisect_left(starts, lo - longest)
    last = bisect.bisect_left(starts, hi)
    here = [x for x in spans[first:last] if x[1] > lo]
    cuts = sorted({lo, hi} | {t for s, e, _ in here for t in (s, e)
                              if lo < t < hi})
    for a, b in zip(cuts, cuts[1:]):
        cover = [x for x in here if x[0] <= a and x[1] >= b]
        yield (max(cover, key=lambda x: (x[0], -x[1]))[2] if cover
               else default), b - a


def idle_by_span(trace: tracing.Trace, program: List[list],
                 device: int = 0) -> Dict[str, float]:
    """:meth:`tracing.Trace.idle_by_label`, with the idle time inside a
    ``bench.*`` label split further by the innermost program span open on
    the host; the part no program span covers keeps the label.  The total
    is the same, and without program spans so is every entry."""
    t0, t1 = trace.window()
    edges = [t0] + [x for iv in trace.busy_intervals(device)
                    for x in iv] + [t1]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    labels = [(n, s, s + d) for n, s, d in trace.host
              if n in tracing.IDLE_LABELS]
    spans = sorted((s, s + d, n) for n, s, d in program)
    starts = [x[0] for x in spans]
    longest = max((x[1] - x[0] for x in spans), default=0)
    out: Dict[str, float] = {}
    for a, b in gaps:
        rest = b - a
        for n, s, e in labels:
            lo, hi = max(a, s), min(b, e)
            if hi > lo:
                for key, ns in _innermost(spans, starts, longest, lo, hi, n):
                    out[key] = out.get(key, 0.0) + ns * 1e-9
                rest -= hi - lo
        if rest > 0:
            out["host_other"] = out.get("host_other", 0.0) + rest * 1e-9
    return out


# -- a fixture: the reduced trace, its program spans and the ring -----------
RING_KEYS = ("name", "ts", "dur", "tid", "id", "parent", "args")


def save(path: str, trace: tracing.Trace, program: List[list],
         ring: List[dict]) -> None:
    with gzip.open(path, "wt") as fh:
        json.dump({**dataclasses.asdict(trace), "program": program,
                   "ring": [{k: e[k] for k in RING_KEYS if k in e}
                            for e in ring]}, fh)


def load(path: str) -> Tuple[tracing.Trace, List[list], List[dict]]:
    with gzip.open(path, "rt") as fh:
        d = json.load(fh)
    program, ring = d.pop("program"), d.pop("ring")
    return tracing.Trace(**d), program, ring


# -- what a traced run's spans say -------------------------------------------
def summary(trace: tracing.Trace, program: List[list],
            ring: List[dict]) -> dict:
    idle = sorted(idle_by_span(trace, program).items(), key=lambda kv: -kv[1])
    names = sorted({n for n, _, _ in program if n.startswith("query.")})
    per_call = {}
    for name in [OUTER] + names:
        secs, calls = span_per_call(trace, program, "bench.search_batch",
                                    name)
        per_call[name] = 1e3 * secs / calls if calls else None
    fetch = [e for e in ring if e["name"] == "query.fetch"]
    nbytes = sum(e["args"].get("bytes", 0) for e in fetch)
    fetch_s = sum(e["dur"] for e in fetch) * 1e-6
    slow = []
    for e in ring:
        if e["name"] == OUTER and e["dur"] > SLOW_MS * 1e3:
            kids = {k["name"]: k["dur"] / 1e3 for k in ring
                    if k.get("parent") == e["id"]}
            slow.append({"ms": e["dur"] / 1e3, "children_ms": kids,
                         "gaps_ms": e["dur"] / 1e3 - sum(kids.values())})
    return {"idle_gaps": [list(x) for x in idle],
            "idle_s": sum(v for _, v in idle),
            "ms_per_call": per_call,
            "calls": sum(1 for e in ring if e["name"] == OUTER),
            "fetch_bytes_per_call": nbytes / len(fetch) if fetch else None,
            "fetch_gb_per_s": nbytes / fetch_s / 1e9 if fetch_s else None,
            "slow_calls": slow}


def main(argv=None) -> int:
    import argparse
    import shutil

    from chipbench import run as bench_run

    class SpanTracer(bench_run.Tracer):
        """:class:`run.Tracer` that also keeps the program's spans from
        the profiler trace before its directory goes."""
        program: List[list] = []

        def finish(self) -> None:
            if self.state != "on":
                return
            import jax
            from repro import obs
            self._ann.__exit__(None, None, None)
            self.spans = obs.events()
            obs.disable()
            jax.profiler.stop_trace()
            path = tracing.find_xplane(str(self.dir))
            self.trace = tracing.reduce_xplane(path)
            self.program = program_spans(path)
            shutil.rmtree(self.dir, ignore_errors=True)
            self.state = "done"

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fixture", default=None,
                    help="write the reduced trace with its spans here")
    args = ap.parse_args(argv)
    tracers: List[SpanTracer] = []
    bench_run.Tracer = lambda *a, **kw: tracers.append(
        SpanTracer(*a, **kw)) or tracers[-1]
    try:
        result = bench_run.run(bench_run.load_cell(args.workload), args.seed,
                               args.seconds, True)
    except bench_run.NoChip as e:
        print(f"spans.py: {e}; nothing was measured", file=sys.stderr)
        return 3
    t = tracers[-1]
    result["spans"] = summary(t.trace, t.program, t.spans)
    if args.fixture:
        save(args.fixture, t.trace, t.program, t.spans)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
