"""Instrumentation decorator for public kernel launch wrappers.

``@instrumented("icws_sketch")`` wraps a public ``repro.kernels.ops``
launch.  With observability disabled the wrapper is a strict pass-through
(one module-level bool read, then tail-call the launch), so jit'd paths and
all bitwise identities are untouched.  When enabled, each call records:

* ``ops.launches_total{op, family, packed}`` -- launch count, attributed
  to the ambient :func:`repro.obs.metrics.family_context` if one is
  active; ``packed`` says whether the corpus value plane arrived as a
  packed store's bf16 plane (``"-"`` for ops without one);
* one span ``ops.<op>`` (:func:`repro.obs.trace.span`): the ring event
  and, under a running profiler, a host annotation beside the device ops
  it enqueued.  On an async backend it times host dispatch, not the
  kernel; kernel time comes from the device trace.  A backend compile
  inside it counts into ``ops.compiles_total{op}``.

The decorator lives in :mod:`repro.obs`, not in ``ops.py`` itself, so the
OB001 analysis rule can require every public def in ``ops.py`` to carry it
without exempting helper definitions.
"""
from __future__ import annotations

import functools
from typing import Optional

from repro.obs import metrics as _m
from repro.obs import trace as _t


def instrumented(op: str, packed_arg: Optional[int] = None):
    """Decorate a public launch wrapper with telemetry under name ``op``.
    ``packed_arg`` is the position of the corpus value plane, for the
    launches that read a store either unpacked (f32) or packed (bf16)."""

    def deco(fn):
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
            with _t.span("ops." + op, family=family):
                return fn(*args, **kwargs)

        wrapper.obs_op = op
        wrapper.__wrapped__ = fn
        return wrapper

    return deco
