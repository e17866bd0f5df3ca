"""Shared benchmark utilities: timing, CSV emission, method sweep."""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, Iterable, List

import numpy as np

from repro.core import (PAPER_METHODS, SparseVec, inner_fast, make,
                        stack_icws, stack_mh, stack_wmh)
from repro.obs.metrics import Histogram

RECORDS: List[Dict] = []


@functools.lru_cache(maxsize=None)
def device() -> Dict:
    """The device every row ran on, as jax reports it."""
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def emit(name: str, us_per_call: float, derived: str):
    dev = device()
    RECORDS.append({"name": name, "value": float(us_per_call),
                    "derived": derived, "device": dev})
    print(f"{name},{us_per_call:.2f},{derived},{dev['platform']}",
          flush=True)


def timed(fn: Callable, *args, repeat: int = 1):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeat):
        out = fn(*args)
    dt = (time.perf_counter() - t0) / repeat
    return out, dt * 1e6  # microseconds


def timed_median(fn: Callable, *args, repeat: int = 5):
    """(last result, latency Histogram) over ``repeat`` timed calls.

    The percentile-aware twin of :func:`timed` for the gated perf
    comparisons: container CPU contention makes single-shot and min-of-N
    wall clocks flaky, so gates compare ``hist.quantile(0.5)`` -- exact
    while ``repeat`` fits the histogram's raw-sample window (128).
    Seconds, not microseconds: callers scale for display.
    """
    h = Histogram("bench")
    out = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args)
        h.record(time.perf_counter() - t0)
    return out, h


def normalized_error(est: float, true: float, na: float, nb: float) -> float:
    """|est - true| / (||a|| ||b||): the paper's error metric (Section 5)."""
    return abs(est - true) / max(na * nb, 1e-12)


def method_errors(method: str, storage: float, pairs, seeds=range(10)) -> Dict:
    """Average normalized error over pairs x seeds for one method/storage.

    Sampling methods get a fresh seed per trial (the paper averages over 10
    independent trials); each pair is sketched and estimated.
    """
    errs = []
    sketch_us = []
    est_us = []
    for seed in seeds:
        sk = make(method, storage, seed=seed)
        for (va, vb) in pairs:
            (sa, dt1) = timed(sk.sketch, va)
            (sb, dt2) = timed(sk.sketch, vb)
            (est, dt3) = timed(sk.estimate, sa, sb)
            true = inner_fast(va, vb)
            errs.append(normalized_error(est, true, va.norm(), vb.norm()))
            sketch_us.extend([dt1, dt2])
            est_us.append(dt3)
    return {"err": float(np.mean(errs)),
            "err_std": float(np.std(errs)),
            "sketch_us": float(np.mean(sketch_us)),
            "est_us": float(np.mean(est_us))}
