#!/usr/bin/env python3
"""One run of one cell of the chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names its configuration
(``chipbench/configs/<config>.json``) and traffic
(``chipbench/traffic/<traffic>.json``, whose ``kind`` names the module
``chipbench/traffic/<kind>.py`` that generates, drives and reads it);
``chipbench/workloads/<cell>.json`` holds the cell's own numbers (the
limits of its check).  Per-layer metrics are read by
``chipbench/metrics/<metric>.py``.  A run sets up (the
lake ingested through ``SketchSearchService.ingest_many_sharded``, every
shape of the window warmed up), measures for ``--seconds``, checks what the
window returned against the exact reference (:mod:`chipbench.check`), and
prints one JSON object as the last line of stdout.  With ``--trace 1`` a
few seconds of the window are profiled and the line carries the cell's
per-layer metrics instead of its end-to-end ones.

It drives the program only through ``SketchSearchService``: the
constructor, ``ingest_many_sharded``, ``search_batch`` and ``describe``.
Without a TPU it exits non-zero and prints no result.  ``--control``
puts the control (the exact reference computed in bfloat16) in the
program's place after the window: it answers the checked requests with
planted tables, and the line's ``correct`` and ``checks`` are the
control's, which must fail; the program's own verdict and readings go
under ``program``.
"""
from __future__ import annotations

import time

_T0 = time.time()      # set-up is measured from here to the window's start

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Callable, List, Optional  # noqa: E402


HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # libtpu logs to /tmp else
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import check, lakes, mix, reference, roofline  # noqa: E402
from chipbench import tracing  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"        # fixed: the path keys the cache
RUN_DIR = ROOT / ".chipbench_run"      # traces of traced runs
TRACE_S = 4.0          # the traced stretch: the window's last seconds, at most


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    spec: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def serving(self) -> dict:
        return self.config["serving"]


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    if bench is None:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in bench["workloads"]}.get(name)
    if wl is None:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}[wl["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    here = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in here)]
    return Cell(name=name, chips=int(wl["chips"]),
                config=json.loads((ROOT / cfg_file).read_text()),
                traffic=json.loads((HERE / "traffic"
                                    / f"{wl['traffic']}.json").read_text()),
                spec=json.loads((HERE / "workloads"
                                 / f"{name}.json").read_text()),
                end_to_end=e2e, per_layer=layer)


# -- the chip ----------------------------------------------------------------
def require_chips(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: jax sees {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; jax sees "
                     f"{len(devices)}")
    from repro.kernels import ops
    if ops._interpret():
        raise NoChip("Pallas kernels would run in interpret mode")
    roofline.peaks(devices[0].device_kind)      # an unknown chip is an error
    return devices


def enable_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Programs lowered, compiled by the backend, and loaded from the
    persistent cache, counted from JAX's monitoring events."""
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.counts = {"lowered": 0, "compiled": 0, "cache_hits": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, *_args, **_kw):
        if event == self.LOWER:
            self.counts["lowered"] += 1
        elif event == self.COMPILE:
            self.counts["compiled"] += 1

    def _event(self, event, *_args, **_kw):
        if event == self.HIT:
            self.counts["cache_hits"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


# -- tracing -----------------------------------------------------------------
class Tracer:
    """Profiles ``[start, stop)`` seconds of the window (``--trace 1``),
    with the program's ``repro.obs`` spans on for the same stretch.  The
    stretch is the window's end, so that what the profiler's start and stop
    hold up comes after ``start``: requests answered before it saw none."""

    def __init__(self, enabled: bool, seconds: float, directory):
        self.enabled = enabled
        self.start = seconds - min(TRACE_S, 0.5 * seconds)
        self.stop = seconds
        self.dir = pathlib.Path(directory)
        self.state = "idle"
        self.spans: list = []
        self.trace: Optional[tracing.Trace] = None
        self._ann = None

    def poll(self, now: float) -> None:
        if not self.enabled:
            return
        if self.state == "idle" and now >= self.start:
            import jax
            from repro import obs
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir.mkdir(parents=True)
            jax.profiler.start_trace(str(self.dir))
            obs.reset_all()
            obs.enable()
            self._ann = _annotate(tracing.WINDOW)
            self._ann.__enter__()
            self.state = "on"
        elif self.state == "on" and now >= self.stop:
            self.finish()

    def finish(self) -> None:
        if self.state != "on":
            return
        import jax
        from repro import obs
        self._ann.__exit__(None, None, None)
        self.spans = obs.events()
        obs.disable()
        jax.profiler.stop_trace()
        self.trace = tracing.reduce_xplane(tracing.find_xplane(str(self.dir)))
        shutil.rmtree(self.dir, ignore_errors=True)
        self.state = "done"


# -- set-up ------------------------------------------------------------------
def ingest_lake(svc, lake: lakes.Tables, batch: int) -> None:
    for lo in range(0, len(lake), batch):
        svc.ingest_many_sharded(lake.batch(lo, lo + batch), shards=1)


# -- the control -------------------------------------------------------------
def control_answers(cell: Cell, traffic, inputs: mix.Inputs,
                    win: mix.Window, queries, planted) -> tuple:
    """The control in the program's place: the checked requests with
    planted tables, at most ``control_requests`` of them, answered by the
    reference computed in bfloat16 over the lake as it stood at the
    close."""
    s = cell.serving
    index = reference.LakeIndex(traffic.lake_at_close(cell, inputs, win))
    have = [i for i, p in enumerate(planted) if p]
    pick = have[:int(cell.spec["control_requests"])]
    return pick, [index.rank(queries[i], s["top_k"], s["min_join"],
                             dtype=reference.BF16) for i in pick]


# -- metrics -----------------------------------------------------------------
def end_to_end(cell: Cell, have: dict, setup_s: float) -> dict:
    have = {**have, "setup_s": setup_s}
    return {m["name"]: {"value": have[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in have}


@dataclasses.dataclass
class ReadContext:
    """What a per-layer reader (``chipbench/metrics/<name>.py``) gets:
    besides the trace, the window itself and the second of it at which the
    traced stretch began."""
    trace: tracing.Trace
    spans: list
    describe: dict
    service: dict
    peaks: dict
    ingest_batch: int
    window: mix.Window
    traced_from: float
    log: Callable[[str], None]


def _reader(name: str):
    """``metrics/<name>.py``; else the file of the name less its last
    ``.part`` (``device_idle_share.query`` -> ``device_idle_share.py``)."""
    stem = name
    while not (HERE / "metrics" / f"{stem}.py").is_file():
        if "." not in stem:
            raise FileNotFoundError(f"no reader for the metric {name!r}")
        stem = stem.rsplit(".", 1)[0]
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{stem.replace('.', '_')}",
        HERE / "metrics" / f"{stem}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(cell: Cell, ctx: ReadContext) -> dict:
    out = {}
    for m in cell.per_layer:
        value = _reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -- one run -----------------------------------------------------------------
def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        control: bool = False, require_chip: bool = True,
        service_cls=None, log=None, keep_trace: Optional[str] = None,
        cache: bool = True) -> dict:
    """One run of ``cell``; returns the result line's object."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    if require_chip:
        devices = require_chips(cell.chips)
    else:
        import jax
        devices = jax.devices()
    kind = devices[0].device_kind
    if service_cls is None:
        from repro.serve import SketchSearchService as service_cls
    cache = enable_compile_cache() if cache else "off"
    compiles = CompileCounter()
    s = cell.serving
    traffic = mix.load(cell.traffic["kind"])

    inputs = traffic.build(cell.config, cell.traffic, seed, seconds)
    log(f"inputs: {len(inputs.lake)} lake tables, "
        f"{len(inputs.queries)} queries, "
        f"{time.time() - _T0:.1f} s since start; compile cache {cache}")
    svc = service_cls(**cell.config["service"])
    ingest_lake(svc, inputs.lake, s["ingest_batch"])
    log(f"lake ingested: {time.time() - _T0:.1f} s since start")
    traffic.warm_up(cell, svc, inputs, service_cls)
    gc.collect()
    before = compiles.snapshot()
    setup_s = time.time() - _T0
    log(f"set-up {setup_s:.1f} s; compiles in set-up {before}")

    tracer = Tracer(trace, seconds, RUN_DIR / f"trace-{cell.name}")
    win = traffic.window(cell, svc, inputs, seconds, tracer)
    after = compiles.snapshot()
    in_window = {k: after[k] - before[k] for k in after}
    log(f"compiles in window: {in_window}")
    log(f"window: {win.notes}")
    stats = devices[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", -1))
    describe = svc.describe()

    queries, answers, planted = traffic.checked(cell, inputs, win, svc)
    del svc
    gc.collect()
    limits = cell.spec["limits"]
    readings = check.compare(queries, answers, planted, inputs.lookup)
    n_planted = sum(len(p) for p in planted)
    correct = check.verdict(readings, limits, win.failed, n_planted)
    log(f"checked {len(queries)} requests, {n_planted} planted tables")
    program = None
    if control:
        t = time.time()
        pick, ctl = control_answers(cell, traffic, inputs, win, queries,
                                    planted)
        program = {"correct": bool(correct),
                   "checks": check.lines(readings, limits)}
        queries = [queries[i] for i in pick]
        planted = [planted[i] for i in pick]
        readings = check.compare(queries, ctl, planted, inputs.lookup)
        n_planted = sum(len(p) for p in planted)
        correct = check.verdict(readings, limits, 0, n_planted)
        log(f"control: {len(pick)} requests, {n_planted} planted tables, "
            f"answered by the reference in bfloat16 in "
            f"{time.time() - t:.1f} s")
        for k, v in program["checks"].items():
            log(f"program {k}: {v['value']!r} (limit {v['limit']!r})")

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": int(win.attempted),
              "failed": int(win.failed)}
    if trace:
        if tracer.trace is None:
            raise RuntimeError("the window ended before the traced stretch")
        if keep_trace:
            tracer.trace.save(keep_trace)
        ctx = ReadContext(trace=tracer.trace, spans=tracer.spans,
                          describe=describe,
                          service={**cell.config["service"], **s,
                                   **cell.config.get("sketch", {})},
                          peaks=roofline.peaks(kind) if require_chip else {},
                          ingest_batch=s["ingest_batch"], window=win,
                          traced_from=tracer.start, log=log)
        result["metrics"] = per_layer(cell, ctx)
        device["busy_s"] = tracer.trace.busy_s()
        device["window_s"] = tracer.trace.window_s()
        result["device"] = device
        result["breakdown"] = tracer.trace.breakdown()
    else:
        result["metrics"] = end_to_end(cell, traffic.readings(win), setup_s)
        result["device"] = device
    if program is not None:
        result["program"] = program
    result["checks"] = check.lines(readings, limits)
    for k, v in result["checks"].items():
        log(f"check {k}: {v['value']!r} (limit {v['limit']!r})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the control in the program's place")
    ap.add_argument("--keep-trace", default=None,
                    help="write the reduced trace (json.gz) here")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace),
                     control=args.control, keep_trace=args.keep_trace)
    except NoChip as e:
        print(f"run.py: {e}; nothing was measured", file=sys.stderr)
        return 3
    with contextlib.suppress(BrokenPipeError):
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
