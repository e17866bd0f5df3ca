"""Profiler trace -> the numbers the per-layer readers need.

A traced run records a few seconds of the steady window with
``jax.profiler``; the harness marks that stretch with a ``bench.traced``
annotation and wraps its own calls in ``bench.search_batch``,
``bench.ingest_batch`` and ``bench.await_arrival``.  :func:`reduce_xplane`
keeps only what the readers use:

* device ops (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane) with
  their HLO module, and the jitted modules (``XLA Modules``);
* the harness's ``bench.*`` host annotations.

The reduced form is gzipped JSON (:meth:`Trace.save`); the fixture under
``chipbench/fixtures`` is one, recorded on the chip.  All times are ns.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.traced"
IDLE_LABELS = ("bench.search_batch", "bench.ingest_batch",
               "bench.await_arrival")


@dataclasses.dataclass
class Trace:
    ops: List[list]            # [device, name, module, start, dur]
    modules: List[list]        # [device, name, start, dur]
    host: List[list]           # [name, start, dur]
    devices: int

    # -- persistence (gzipped JSON) ------------------------------------------
    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as fh:
            json.dump(dataclasses.asdict(self), fh)

    @classmethod
    def load(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as fh:
            return cls(**json.load(fh))

    # -- the traced window ---------------------------------------------------
    def window(self) -> Tuple[float, float]:
        spans = [(s, s + d) for n, s, d in self.host if n == WINDOW]
        if not spans:
            raise ValueError(f"trace has no {WINDOW} annotation")
        return spans[0]

    def window_s(self) -> float:
        t0, t1 = self.window()
        return (t1 - t0) * 1e-9

    def _clip(self, s: float, d: float) -> Optional[Tuple[float, float]]:
        t0, t1 = self.window()
        a, b = max(s, t0), min(s + d, t1)
        return (a, b) if b > a else None

    # -- device time ---------------------------------------------------------
    def busy_intervals(self, device: int) -> List[Tuple[float, float]]:
        """Union of the device's op intervals inside the window."""
        iv = sorted(c for dev, _, _, s, d in self.ops if dev == device
                    for c in [self._clip(s, d)] if c)
        out: List[List[float]] = []
        for a, b in iv:
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return [tuple(x) for x in out]

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        tot = sum(b - a for dev in range(self.devices)
                  for a, b in self.busy_intervals(dev))
        return tot * 1e-9 / max(self.devices, 1)

    def per_call(self, host: str, pattern: str, modules: bool = False
                 ) -> Tuple[float, int]:
        """Device seconds of the ops (or jitted modules) matching
        ``pattern`` that start inside the ``host`` annotations lying wholly
        in the window, and the number of those annotations.  Per call, so
        a kernel split into more launches reads the same."""
        t0, t1 = self.window()
        calls = [(s, s + d) for n, s, d in self.host
                 if n == host and s >= t0 and s + d <= t1]
        rx = re.compile(pattern)
        if modules:
            events = [(s, d) for _, name, s, d in self.modules
                      if rx.search(name)]
        else:
            events = [(s, d) for _, name, module, s, d in self.ops
                      if rx.search(name) or rx.search(module or "")]
        tot = sum(d for s, d in events
                  if any(a <= s < b for a, b in calls))
        return tot * 1e-9, len(calls)

    # -- breakdown -----------------------------------------------------------
    def idle_by_label(self, device: int = 0) -> Dict[str, float]:
        """Idle seconds of ``device`` inside the window, split by the
        harness annotation the host was in (``host_other`` outside them)."""
        t0, t1 = self.window()
        edges = [t0] + [x for iv in self.busy_intervals(device)
                        for x in iv] + [t1]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        labels = [(n, s, s + d) for n, s, d in self.host
                  if n in IDLE_LABELS]
        out: Dict[str, float] = {}
        for a, b in gaps:
            rest = b - a
            for n, s, e in labels:
                cut = min(b, e) - max(a, s)
                if cut > 0:
                    out[n] = out.get(n, 0.0) + cut * 1e-9
                    rest -= cut
            if rest > 0:
                out["host_other"] = out.get("host_other", 0.0) + rest * 1e-9
        return out

    def breakdown(self, top: int = 10) -> dict:
        per: Dict[str, float] = {}
        for _, name, module, s, d in self.ops:
            c = self._clip(s, d)
            if c:
                key = f"{module}:{name}" if module else name
                per[key] = per.get(key, 0.0) + (c[1] - c[0]) * 1e-9
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_label().items(), key=lambda kv: -kv[1])
        return {"device_ops": [list(x) for x in ops],
                "idle_gaps": [list(x) for x in idle[:top]]}


def short_name(name: str) -> str:
    """An op's HLO instruction name, without the instruction text that a
    TPU trace appends (``%sort.1 = (s32[16,262144]...) sort(...)``)."""
    return name.split(" = ", 1)[0]


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def attribute_modules(ops: List[list], modules: List[list]) -> None:
    """Fill in each op's jitted module, where the trace left it out, from
    the module run that was under way on its device when it started."""
    runs: Dict[int, list] = {}
    for dev, name, s, d in sorted(modules, key=lambda m: m[2]):
        runs.setdefault(dev, []).append((s, s + d, name.split("(", 1)[0]))
    starts = {dev: [r[0] for r in rs] for dev, rs in runs.items()}
    for op in ops:
        if op[2] or op[0] not in runs:
            continue
        i = bisect.bisect_right(starts[op[0]], op[3]) - 1
        if i >= 0 and op[3] < runs[op[0]][i][1]:
            op[2] = runs[op[0]][i][2]


def reduce_xplane(path: str) -> Trace:
    """Read one ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    devices = 0
    for plane in data.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            dev = int(m.group(1))
            devices = max(devices, dev + 1)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        st = _stats(e)
                        ops.append([dev, short_name(e.name),
                                    str(st.get("hlo_module", "")),
                                    e.start_ns, e.duration_ns])
                elif line.name == "XLA Modules":
                    for e in line.events:
                        modules.append([dev, e.name, e.start_ns,
                                        e.duration_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, e.start_ns, e.duration_ns])
    attribute_modules(ops, modules)
    return Trace(ops=ops, modules=modules, host=host, devices=devices)


def find_xplane(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]
