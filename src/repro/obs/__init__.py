"""Opt-in, zero-dependency observability for the sketch serving stack.

Enable with ``REPRO_OBS=1`` in the environment (or :func:`enable` at
runtime).  While disabled -- the default -- every instrumented path is a
strict no-op: one bool read per call, no metric writes, no spans, and the
jit'd numerics are bitwise untouched.

Pieces:

* :mod:`repro.obs.metrics` -- counters, gauges, mergeable log-bucket
  latency histograms; ``describe_metrics()`` / Prometheus exporters.
* :mod:`repro.obs.trace` -- structured spans, Chrome-trace / JSONL export;
  enabled spans also reach a running JAX profiler trace.
* :mod:`repro.obs.quality` -- sampled estimator re-scores, rolling
  ppm-error gauge per family.
* :mod:`repro.obs.instrument` -- the ``@instrumented`` decorator applied
  to every public launch in ``repro.kernels.ops`` (enforced by analysis
  rule OB001).
* ``python -m repro.obs`` -- pretty-print a metrics dump or diff two.

Every metric name is declared in :mod:`repro.obs.registry`; the generated
``METRICS.md`` is pinned against that registry by analysis rule OB002.

This package is pure stdlib (no jax import) so the static-analysis pass
and the CLI stay usable on machines without the accelerator stack; jax is
imported lazily, once obs is enabled, and only where it is installed.
"""
from __future__ import annotations

import os

from repro.obs.instrument import instrumented
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    counter,
    current_family,
    describe_metrics,
    disable,
    enable,
    enabled,
    family_context,
    gauge,
    histogram,
    prometheus_text,
    reset,
    save_metrics,
)
from repro.obs.quality import record_sample, reset_quality, rolling_ppm
from repro.obs.registry import SPECS
from repro.obs.trace import (
    chrome_trace,
    events,
    reset_trace,
    save_chrome_trace,
    save_jsonl,
    span,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "SPECS",
    "chrome_trace", "counter", "current_family", "describe_metrics",
    "disable", "enable", "enabled", "events", "export_snapshot",
    "family_context", "gauge", "histogram", "instrumented",
    "prometheus_text", "record_sample", "reset", "reset_all",
    "reset_quality", "reset_trace", "rolling_ppm", "save_chrome_trace",
    "save_jsonl", "save_metrics", "span",
]


def reset_all() -> None:
    """Clear metrics, the trace ring, and the quality EWMA state."""
    reset()
    reset_trace()
    reset_quality()


def export_snapshot(directory: str | None = None) -> dict:
    """Write metrics.json + trace.json (Chrome) + trace.jsonl to a directory.

    ``directory`` defaults to ``$REPRO_OBS_DIR`` or ``obs_snapshot``.
    Returns the written paths keyed by artifact name.
    """
    directory = directory or os.environ.get("REPRO_OBS_DIR") or "obs_snapshot"
    os.makedirs(directory, exist_ok=True)
    paths = {
        "metrics": os.path.join(directory, "metrics.json"),
        "chrome_trace": os.path.join(directory, "trace.json"),
        "jsonl": os.path.join(directory, "trace.jsonl"),
    }
    save_metrics(paths["metrics"])
    save_chrome_trace(paths["chrome_trace"])
    save_jsonl(paths["jsonl"])
    return paths
