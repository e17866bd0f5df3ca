"""Structured spans: an in-memory ring buffer and the JAX profiler's trace.

``span("ops.icws_estimate_fields", family="icws", backend="cpu")`` times a
block and, when observability is enabled, appends one *complete* event to a
bounded ring buffer.  Each event carries an ``id`` and the ``parent`` id of
the span that was open around it on the same thread (``None`` at the top),
so a consumer can tell the span that caused it.  The ring exports two ways:

* :func:`chrome_trace` / :func:`save_chrome_trace` -- Chrome trace-event
  JSON (``chrome://tracing`` / Perfetto ``X`` phase events, microsecond
  timestamps relative to process start);
* :func:`save_jsonl` -- one flat JSON object per line for ad-hoc grepping.

An enabled span is also a ``jax.profiler.TraceAnnotation`` of its plain
name, so while a profiler trace is running the span lands in it on the
profiler's host clock, the clock the device planes share: a device idle gap
can be put down to the span the host was in.  ``jax`` is imported lazily,
once, on the first enabled span or :func:`repro.obs.enable`; without it
spans go to the ring alone.  The same binding registers the listener that
counts backend compiles into ``ops.compiles_total{op}``, labelled by the
innermost open ``ops.*`` span of the compiling thread.

When observability is disabled, :func:`span` returns a shared null context:
no allocation, no clock reads, no annotation, no ring append -- the
instrumented block runs exactly as before.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque

from repro.obs import metrics as _m

RING_CAPACITY = int(os.environ.get("REPRO_OBS_RING", "4096"))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_EPOCH = time.perf_counter()
_RING: deque = deque(maxlen=RING_CAPACITY)
_PID = os.getpid()
_IDS = itertools.count(1)
_TLS = threading.local()
# jax.profiler.TraceAnnotation once bound; False where jax is missing
_ANNOTATION = None


class _NullSpan:
    """Shared no-op span for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, key, value):
        pass


_NULL = _NullSpan()


def _open_spans() -> list:
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    return stack


def _innermost_op() -> str:
    for s in reversed(_open_spans()):
        if s.name.startswith("ops."):
            return s.name[len("ops."):]
    return "-"


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == COMPILE_EVENT and _m.enabled():
        _m.counter("ops.compiles_total", op=_innermost_op()).inc()


def bind_jax():
    """Look up the profiler's annotation and register the compile listener,
    once; returns the annotation class, or False where jax is missing."""
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            import jax.monitoring
            import jax.profiler
        except ImportError:
            _ANNOTATION = False
        else:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _ANNOTATION = jax.profiler.TraceAnnotation
    return _ANNOTATION


class Span:
    __slots__ = ("name", "args", "id", "parent", "_t0", "_ann")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args
        self.id = next(_IDS)
        self.parent = None
        self._t0 = 0.0
        self._ann = None

    def set(self, key: str, value) -> None:
        """Attach an attribute discovered mid-span (e.g. a result size)."""
        self.args[key] = value

    def __enter__(self):
        stack = _open_spans()
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        annotation = bind_jax()
        if annotation:
            self._ann = annotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        _open_spans().pop()
        event = {
            "name": self.name,
            "ph": "X",
            "cat": self.name.split(".", 1)[0],
            "ts": (self._t0 - _EPOCH) * 1e6,
            "dur": (t1 - self._t0) * 1e6,
            "pid": _PID,
            "tid": threading.get_ident() % 1_000_000,
            "id": self.id,
            "parent": self.parent,
            "args": {k: _jsonable(v) for k, v in self.args.items()},
        }
        if exc_type is not None:
            event["args"]["error"] = exc_type.__name__
        _RING.append(event)
        return False


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    return str(v)


def span(name: str, **attrs):
    """Time a block as a structured span; a strict no-op when disabled."""
    if not _m.enabled():
        return _NULL
    return Span(name, attrs)


def events() -> list:
    """Current ring contents, oldest first."""
    return list(_RING)


def reset_trace() -> None:
    _RING.clear()


def chrome_trace() -> dict:
    return {"traceEvents": events(), "displayTimeUnit": "ms"}


def save_chrome_trace(path: str) -> None:
    with open(path, "w") as fh:
        json.dump(chrome_trace(), fh)
        fh.write("\n")


def save_jsonl(path: str) -> None:
    with open(path, "w") as fh:
        for event in _RING:
            fh.write(json.dumps(event, sort_keys=True))
            fh.write("\n")
