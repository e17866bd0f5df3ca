"""Instrumentation decorator for public kernel launch wrappers.

``@instrumented("icws_sketch")`` wraps a public ``repro.kernels.ops``
launch.  With observability disabled the wrapper is a strict pass-through
(one module-level bool read, then tail-call the launch), so jit'd paths and
all bitwise identities are untouched.  When enabled, each call records:

* ``ops.launches_total{op, family, packed}`` -- launch count, attributed
  to the ambient :func:`repro.obs.metrics.family_context` if one is
  active; ``packed`` says whether the corpus value plane arrived as a
  packed store's bf16 plane (``"-"`` for ops without one);
* ``ops.first_call_seconds{op}`` -- the first observed call per op (jit
  trace + compile + execute), split from steady state;
* ``ops.launch_seconds{op, family, packed}`` -- every subsequent call;
* one complete trace event ``ops.<op>`` in the span ring.

Wall times measure host-side dispatch on async backends; under the CPU
Pallas interpreter (the default everywhere but TPU) dispatch is effectively
synchronous, so they are end-to-end latencies there.

The decorator lives in :mod:`repro.obs`, not in ``ops.py`` itself, so the
OB001 analysis rule can require every public def in ``ops.py`` to carry it
without exempting helper definitions.
"""
from __future__ import annotations

import functools
import time
from typing import Optional

from repro.obs import metrics as _m
from repro.obs import trace as _t


def instrumented(op: str, packed_arg: Optional[int] = None):
    """Decorate a public launch wrapper with telemetry under name ``op``.
    ``packed_arg`` is the position of the corpus value plane, for the
    launches that read a store either unpacked (f32) or packed (bf16)."""

    def deco(fn):
        state = {"first_seen": False}

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not _m.enabled():
                return fn(*args, **kwargs)
            family = _m.current_family()
            plane = (args[packed_arg] if packed_arg is not None
                     and packed_arg < len(args) else None)
            packed = "-" if plane is None else str(
                str(getattr(plane, "dtype", "")) == "bfloat16").lower()
            _m.counter("ops.launches_total", op=op, family=family,
                       packed=packed).inc()
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            t1 = time.perf_counter()
            dt = t1 - t0
            if state["first_seen"]:
                _m.histogram("ops.launch_seconds", op=op, family=family,
                             packed=packed).record(dt)
            else:
                state["first_seen"] = True
                _m.histogram("ops.first_call_seconds", op=op).record(dt)
            _t.add_complete_event("ops." + op, t0, t1, {"family": family})
            return out

        wrapper.obs_op = op
        wrapper.__wrapped__ = fn
        return wrapper

    return deco
