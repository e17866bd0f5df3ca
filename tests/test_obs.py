"""Observability layer: no-op guarantee, histogram algebra, exporters.

The two contracts that matter most, tested end to end through the real
serving stack: (1) with observability DISABLED (the default), every
instrumented path is a strict pass-through -- rankings are bitwise
identical with obs on and off, and the decorator adds only an enabled()
check; (2) with observability ENABLED, every launch/endpoint records into
the declared metric namespace and the trace ring, and batched==sequential
still holds through the instrumented launches.  Plus the unit algebra:
log-bucket layout, exact-window quantiles, bucketwise merge, registry
validation, quality EWMA, Chrome-trace schema, Prometheus text, snapshot
export, and the ``python -m repro.obs`` CLI.
"""
import json
import math

import numpy as np
import pytest

from repro import obs
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.__main__ import main as obs_cli
from repro.obs.metrics import (N_FINITE, RECENT_WINDOW, Histogram,
                               bucket_bounds, bucket_index)
from repro.obs.quality import EWMA_ALPHA
from repro.serve import SketchSearchService


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Every test starts disabled with empty state and leaves it that way."""
    was = obs.enabled()
    obs.disable()
    obs.reset_all()
    yield
    if was:
        obs.enable()
    else:
        obs.disable()
    obs.reset_all()


# ---------------------------------------------------------------------------
# histogram bucket layout + quantiles + merge algebra
# ---------------------------------------------------------------------------

def test_bucket_index_layout():
    assert bucket_index(0.0) == 0
    assert bucket_index(-1.0) == 0
    assert bucket_index(1e-9) == 0                       # underflow
    assert bucket_index(1e9) == N_FINITE + 1             # overflow
    # monotone non-decreasing across 12 decades
    idxs = [bucket_index(10.0 ** e) for e in np.linspace(-8, 4, 200)]
    assert idxs == sorted(idxs)
    # every finite bucket's bounds actually contain values mapped to it
    for i in range(1, N_FINITE + 1):
        lo, hi = bucket_bounds(i)
        mid = math.sqrt(lo * hi)
        assert bucket_index(mid) == i, (i, lo, hi)


def test_histogram_exact_quantiles_within_window():
    h = Histogram("t")
    vals = [0.001 * (i + 1) for i in range(100)]         # fits the window
    for v in vals:
        h.record(v)
    assert h.count == 100 and len(h.recent) == 100
    assert h.quantile(0.5) == pytest.approx(vals[49])
    assert h.quantile(0.99) == pytest.approx(vals[98])
    assert h.min == pytest.approx(vals[0])
    assert h.max == pytest.approx(vals[-1])
    assert h.mean == pytest.approx(sum(vals) / 100)


def test_histogram_bucket_fallback_clamped():
    h = Histogram("t")
    for i in range(3 * RECENT_WINDOW):                   # overflow the window
        h.record(0.01 + 0.0001 * i)
    assert len(h.recent) < h.count
    q = h.quantile(0.5)
    assert h.min <= q <= h.max                           # clamped to extremes


def test_histogram_merge_algebra():
    a, b = Histogram("a"), Histogram("b")
    va = [0.001, 0.01, 0.1]
    vb = [0.002, 1.0, 10.0, 0.0005]
    for v in va:
        a.record(v)
    for v in vb:
        b.record(v)
    ref = Histogram("ref")
    for v in va + vb:
        ref.record(v)
    a.merge(b)
    assert a.count == ref.count == 7
    assert a.sum == pytest.approx(ref.sum)
    assert a.min == pytest.approx(ref.min)
    assert a.max == pytest.approx(ref.max)
    assert a.buckets == ref.buckets
    # union still fits the window => quantiles stay exact order statistics
    assert a.quantile(0.5) == pytest.approx(ref.quantile(0.5))
    d = a.as_dict()
    assert d["layout"] == obs_metrics.LAYOUT
    assert len(d["buckets"]) == N_FINITE + 2


def test_histogram_merge_rejects_layout_mismatch():
    a, b = Histogram("a"), Histogram("b")
    b.buckets = b.buckets[:-1]                           # foreign layout
    with pytest.raises(ValueError, match="layout"):
        a.merge(b)


# ---------------------------------------------------------------------------
# registry validation + family context
# ---------------------------------------------------------------------------

def test_registry_validates_name_kind_and_labels():
    with pytest.raises(KeyError, match="undeclared"):
        obs.counter("no.such_metric")
    with pytest.raises(TypeError, match="declared as"):
        obs.gauge("ops.launches_total", op="x", family="y", packed="-")
    with pytest.raises(ValueError, match="requires labels"):
        obs.counter("ops.launches_total", op="x")
    c1 = obs.counter("ops.launches_total", op="x", family="y", packed="-")
    c2 = obs.counter("ops.launches_total", packed="-", family="y", op="x")
    assert c1 is c2                                      # one series per key
    c1.inc(3)
    assert c2.value == 3


def test_family_context_nesting():
    assert obs.current_family() == "-"
    with obs.family_context("icws"):
        assert obs.current_family() == "icws"
        with obs.family_context("ts"):
            assert obs.current_family() == "ts"
        assert obs.current_family() == "icws"
    assert obs.current_family() == "-"


# ---------------------------------------------------------------------------
# the no-op guarantee and the instrumented decorator
# ---------------------------------------------------------------------------

def test_disabled_paths_are_strict_noops():
    assert not obs.enabled()
    calls = []
    wrapped = obs.instrumented("icws_estimate")(lambda x: calls.append(x) or x)
    assert wrapped(7) == 7 and calls == [7]
    assert obs.record_sample("icws", 1.0, 2.0) is None
    s1 = obs.span("store.append", family="icws")
    s2 = obs.span("merge.merge_stores")
    assert s1 is s2                                      # shared null span
    with s1 as sp:
        sp.set("rows", 3)
    assert obs.events() == []
    assert obs.describe_metrics()["metrics"] == {}       # nothing registered


def test_instrumented_records_counts_latency_and_trace():
    obs.enable()
    wrapped = obs.instrumented("icws_estimate")(lambda: 42)
    with obs.family_context("ts"):
        assert wrapped() == 42                           # first call
        assert wrapped() == 42                           # steady state
    launches = obs.counter("ops.launches_total", op="icws_estimate",
                           family="ts", packed="-")
    assert launches.value == 2
    assert set(obs.describe_metrics()["metrics"]) == {"ops.launches_total"}
    evts = [e for e in obs.events() if e["name"] == "ops.icws_estimate"]
    assert len(evts) == 2
    assert all(e["args"]["family"] == "ts" and e["dur"] >= 0.0
               for e in evts)


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records its calls."""
    calls: list = []

    def __init__(self, name):
        self.name = name
        self.calls.append(("new", name))

    def __enter__(self):
        self.calls.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.calls.append(("exit", self.name))
        return False


def test_enabled_span_enters_and_exits_the_profiler_annotation(monkeypatch):
    monkeypatch.setattr(obs_trace, "_ANNOTATION", _Annotation)
    monkeypatch.setattr(_Annotation, "calls", [])
    obs.enable()
    with obs.span("serve.search_batch", batch=3):
        with obs.span("query.fetch") as sp:
            sp.set("bytes", 64)
    assert _Annotation.calls == [
        ("new", "serve.search_batch"), ("enter", "serve.search_batch"),
        ("new", "query.fetch"), ("enter", "query.fetch"),
        ("exit", "query.fetch"), ("exit", "serve.search_batch")]


def test_disabled_span_reads_no_clock_and_enters_no_annotation(monkeypatch):
    monkeypatch.setattr(obs_trace, "_ANNOTATION", _Annotation)
    monkeypatch.setattr(_Annotation, "calls", [])

    def clock():
        raise AssertionError("a disabled span read the clock")
    monkeypatch.setattr(obs_trace.time, "perf_counter", clock)
    with obs.span("query.prep") as sp:
        assert sp is obs_trace._NULL
    assert _Annotation.calls == [] and obs.events() == []


def test_ring_events_carry_id_and_parent():
    obs.enable()
    with obs.span("serve.search_batch"):
        with obs.span("query.dispatch"):
            with obs.span("ops.top_k"):
                pass
        with obs.span("query.wait"):
            pass
    with obs.span("serve.search"):
        pass
    by = {e["name"]: e for e in obs.events()}
    assert len({e["id"] for e in by.values()}) == 5
    top = by["serve.search_batch"]["id"]
    assert by["serve.search_batch"]["parent"] is None
    assert by["serve.search"]["parent"] is None
    assert by["query.dispatch"]["parent"] == top
    assert by["query.wait"]["parent"] == top
    assert by["ops.top_k"]["parent"] == by["query.dispatch"]["id"]


def test_import_repro_obs_does_not_import_jax():
    import os
    import subprocess
    import sys
    code = ("import sys, repro.obs; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    env = {**os.environ, "REPRO_OBS": "1",
           "PYTHONPATH": os.pathsep.join(sys.path)}
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=120).returncode == 0


def test_compiles_are_counted_by_the_innermost_ops_span():
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a hit in a persistent cache would load the program, not compile it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        obs.enable()
        step = obs.instrumented("compile_probe")(
            jax.jit(lambda x: x * 3.0 + 1.0))
        step(jnp.zeros(5))
        step(jnp.zeros(5))                               # cached
        step(jnp.zeros(7))                               # a new shape
        assert obs.counter("ops.compiles_total",
                           op="compile_probe").value == 2
        jax.jit(lambda x: x - 2.0)(jnp.zeros(3))         # outside any op
        assert obs.counter("ops.compiles_total", op="-").value >= 1
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


def test_instrumented_labels_packed_value_planes():
    """A launch that reads a store either way labels its series with
    whether the value plane arrived packed (bf16) or not."""
    import jax.numpy as jnp
    obs.enable()
    wrapped = obs.instrumented("icws_estimate_fields", packed_arg=1)(
        lambda a, b: b)
    with obs.family_context("icws"):
        wrapped(0, jnp.zeros(2, jnp.bfloat16))
        wrapped(0, jnp.zeros(2, jnp.bfloat16))
        wrapped(0, jnp.zeros(2, jnp.float32))

    def count(packed):
        return obs.counter("ops.launches_total", op="icws_estimate_fields",
                           family="icws", packed=packed).value
    assert (count("true"), count("false")) == (2, 1)


def test_quality_ewma_arithmetic():
    obs.enable()
    # scale=1e6 => ppm == |est - ref|
    first = obs.record_sample("jl", 3.0, 1.0, scale=1e6)
    assert first == pytest.approx(2.0)
    second = obs.record_sample("jl", 6.0, 1.0, scale=1e6)
    assert second == pytest.approx(EWMA_ALPHA * 5.0 + (1 - EWMA_ALPHA) * 2.0)
    assert obs.rolling_ppm("jl") == pytest.approx(second)
    assert obs.rolling_ppm("cs") is None
    assert obs.counter("quality.samples_total", family="jl").value == 2
    assert obs.gauge("quality.ppm_error",
                     family="jl").value == pytest.approx(second)


# ---------------------------------------------------------------------------
# exporters: describe / prometheus / chrome trace / snapshot / CLI
# ---------------------------------------------------------------------------

def test_chrome_trace_schema_and_error_capture():
    obs.enable()
    with obs.span("store.append", family="icws", rows=4) as sp:
        sp.set("tenant", "a")
    with pytest.raises(RuntimeError):
        with obs.span("merge.merge_stores", family="ts"):
            raise RuntimeError("boom")
    trace = obs.chrome_trace()
    assert trace["displayTimeUnit"] == "ms"
    evts = trace["traceEvents"]
    assert [e["name"] for e in evts] == ["store.append", "merge.merge_stores"]
    for e in evts:
        assert e["ph"] == "X"
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["cat"] == e["name"].split(".")[0]
        json.dumps(e)                                    # fully serializable
    assert evts[0]["args"] == {"family": "icws", "rows": 4, "tenant": "a"}
    assert evts[1]["args"]["error"] == "RuntimeError"


def test_prometheus_text_format():
    obs.enable()
    obs.counter("serve.queries_total").inc(5)
    h = obs.histogram("serve.request_seconds", endpoint="search")
    h.record(0.01)
    h.record(0.02)
    text = obs.prometheus_text()
    assert "# HELP repro_serve_queries_total" in text
    assert "# TYPE repro_serve_queries_total counter" in text
    assert "repro_serve_queries_total 5" in text
    assert 'repro_serve_request_seconds_bucket{endpoint="search",le="+Inf"} 2' \
        in text
    assert 'repro_serve_request_seconds_count{endpoint="search"} 2' in text
    # cumulative bucket counts are non-decreasing
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("repro_serve_request_seconds_bucket")]
    assert cums == sorted(cums) and cums[-1] == 2


def test_export_snapshot_and_cli(tmp_path, capsys):
    obs.enable()
    obs.counter("serve.queries_total").inc(2)
    obs.histogram("serve.request_seconds", endpoint="search").record(0.01)
    with obs.span("store.append", family="icws"):
        pass
    paths = obs.export_snapshot(str(tmp_path / "snap"))
    snap = json.loads(open(paths["metrics"]).read())
    assert snap["version"] == 1 and snap["enabled"] is True
    assert "serve.queries_total" in snap["metrics"]
    trace = json.loads(open(paths["chrome_trace"]).read())
    assert trace["traceEvents"][0]["name"] == "store.append"
    assert open(paths["jsonl"]).read().count("\n") == 1

    assert obs_cli(["show", paths["metrics"]]) == 0
    out = capsys.readouterr().out
    assert "serve.queries_total" in out and "p50=" in out

    obs.counter("serve.queries_total").inc(3)
    after = tmp_path / "after.json"
    obs.save_metrics(str(after))
    assert obs_cli(["diff", paths["metrics"], str(after)]) == 0
    out = capsys.readouterr().out
    assert "+3 (2 -> 5)" in out
    assert obs_cli(["diff", str(after), str(after)]) == 0
    assert "(no differences)" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# end to end through the serving stack (jax; instrumented ops launches)
# ---------------------------------------------------------------------------

def _small_service():
    svc = SketchSearchService(m=32, seed=7, keep_host_oracle=False)
    rng = np.random.default_rng(17)
    keys = np.arange(60)
    sig = rng.normal(size=60)
    for t in range(6):
        svc.ingest(f"t{t}", keys, sig + (0.1 + 0.2 * t) * rng.normal(size=60))
    queries = [(keys, sig + 0.1 * rng.normal(size=60)) for _ in range(4)]
    return svc, queries


def test_rankings_bitwise_identical_obs_on_and_off():
    """The acceptance contract: enabling obs cannot change a single bit of
    what the instrumented launches compute."""
    svc_off, queries = _small_service()
    res_off = [svc_off.search(k, v, top_k=3, min_join=5) for k, v in queries]

    obs.enable()
    svc_on, queries_on = _small_service()
    res_on = [svc_on.search(k, v, top_k=3, min_join=5) for k, v in queries_on]
    assert res_on == res_off
    # and the telemetry actually recorded while producing identical bits
    snap = obs.describe_metrics()["metrics"]
    assert snap["ops.launches_total"]["series"]
    assert any(s["labels"]["endpoint"] == "search"
               for s in snap["serve.request_seconds"]["series"])
    assert obs.counter("serve.queries_total").value == len(queries)
    assert any(e["name"].startswith("ops.") for e in obs.events())


def test_batched_equals_sequential_with_obs_enabled():
    obs.enable()
    svc, queries = _small_service()
    seq = [svc.search(k, v, top_k=3, min_join=5) for k, v in queries]
    bat = svc.search_batch(queries, top_k=3, min_join=5, micro_batch=4)
    assert bat == seq
    assert obs.counter("serve.batch_queries_total").value == len(queries)


def test_describe_true_ints_and_latency_percentiles():
    svc, queries = _small_service()          # obs disabled: stats still work
    for k, v in queries:
        svc.search(k, v, top_k=3, min_join=5)
    d = svc.describe()
    for key in ("tables", "tenants", "corpus_rows", "queries_served"):
        assert isinstance(d[key], int), key
    assert d["tables"] == 6 and d["queries_served"] == 4
    for key in ("query_ms_p50", "query_ms_p95", "query_ms_p99"):
        assert isinstance(d[key], float) and d[key] > 0.0, key
    assert d["query_ms_p50"] <= d["query_ms_p99"]
    # private per-service stats: a second service starts from zero
    svc2, _ = _small_service()
    assert svc2.describe()["queries_served"] == 0
