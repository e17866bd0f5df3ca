"""The program's spans in a traced run: the idle split by span and the
per-call span time on a hand-made trace, the readers of the obs ring, the
spans of a real profiler trace on the CPU, and the traced run recorded on
the chip with its spans and committed as a fixture."""
import copy
import json
import pathlib

import pytest

import chipbench_tiny
from chipbench import spans, tracing
from chipbench import run as bench_run

MS = 1_000_000                      # ns

FIXTURES = bench_run.HERE / "fixtures"
OLD = FIXTURES / "w1_query_open.trace.json.gz"
NEW = FIXTURES / "w1_query_open.spans.trace.json.gz"
NEW_METRICS = ("query_prep_ms_per_batch.query",
               "d2h_copy_ms_per_batch.query", "rerank_ms_per_batch.query")


def _hand_trace() -> tracing.Trace:
    """A 100 ms window with two search_batch calls of 30 ms (10-40 and
    60-90 ms); the device runs 20-30 and 70-80 ms."""
    host = [[tracing.WINDOW, 0, 100 * MS],
            ["bench.search_batch", 10 * MS, 30 * MS],
            ["bench.await_arrival", 40 * MS, 20 * MS],
            ["bench.search_batch", 60 * MS, 30 * MS]]
    ops = [[0, "%estimate_fields_pallas.1", "", s * MS, 10 * MS]
           for s in (20, 70)]
    return tracing.Trace(ops=ops, modules=[], host=host, devices=1)


# program spans of the two calls: the service span, and its children; an
# ops launch nests inside the dispatch of the first call
PROGRAM = [
    ["serve.search_batch", 11 * MS, 28 * MS],
    ["query.prep", 12 * MS, 5 * MS],
    ["query.dispatch", 17 * MS, 2 * MS],
    ["ops.icws_estimate_fields", 18 * MS, 1 * MS],
    ["query.wait", 19 * MS, 12 * MS],
    ["query.fetch", 31 * MS, 3 * MS],
    ["query.rerank", 34 * MS, 4 * MS],
    ["serve.search_batch", 61 * MS, 28 * MS],
    ["query.prep", 61 * MS, 6 * MS],
    ["query.wait", 67 * MS, 14 * MS],
    ["query.rerank", 81 * MS, 8 * MS],
]


def test_the_idle_split_keeps_the_total_and_the_innermost_span_wins():
    t = _hand_trace()
    old = t.idle_by_label()
    new = spans.idle_by_span(t, PROGRAM)
    assert sum(new.values()) == pytest.approx(sum(old.values()), abs=1e-9)
    assert new["bench.await_arrival"] == old["bench.await_arrival"]
    assert new["host_other"] == old["host_other"]
    split = {k: v for k, v in new.items() if k not in old}
    assert new["bench.search_batch"] + sum(split.values()) == pytest.approx(
        old["bench.search_batch"], abs=1e-9)
    assert split == {
        "serve.search_batch": pytest.approx(0.002),   # 11-12, 38-39
        "query.prep": pytest.approx(0.011),           # 12-17, 61-67
        "query.dispatch": pytest.approx(0.001),       # 17-18; 18-19 is ops
        "ops.icws_estimate_fields": pytest.approx(0.001),
        "query.wait": pytest.approx(0.006),   # 19-20, 30-31, 67-70, 80-81
        "query.fetch": pytest.approx(0.003),
        "query.rerank": pytest.approx(0.012),         # 34-38, 81-89
    }
    # what no program span covers keeps the harness's label: 10-11,
    # 39-40, 60-61, 89-90
    assert new["bench.search_batch"] == pytest.approx(0.004)


def test_without_program_spans_the_split_is_the_parents_dict():
    t = _hand_trace()
    assert spans.idle_by_span(t, []) == t.idle_by_label()


# the committed fixture's breakdown as the harness reduced it before the
# program's spans existed
OLD_IDLE_GAPS = [["bench.search_batch", 2.3138859100000526],
                 ["host_other", 0.002933872]]


def test_the_old_fixtures_breakdown_is_unchanged():
    t = tracing.Trace.load(str(OLD))
    assert t.breakdown()["idle_gaps"] == OLD_IDLE_GAPS
    assert spans.idle_by_span(t, []) == t.idle_by_label()


def test_span_per_call_counts_calls_wholly_in_the_window():
    t = _hand_trace()
    t.host.append(["bench.search_batch", 95 * MS, 30 * MS])  # past the end
    program = PROGRAM + [["query.prep", 96 * MS, 5 * MS]]
    assert spans.span_per_call(t, program, "bench.search_batch",
                               "query.prep") == (pytest.approx(0.011), 2)
    assert spans.span_per_call(t, program, "bench.search_batch",
                               "query.fetch") == (pytest.approx(0.003), 2)


def _ring(with_ids: bool = True) -> list:
    """The obs ring of the hand trace's calls (ts/dur in us), with the ids
    a span carries; without them as the spans of an older program."""
    events = []
    stack = []
    for i, (name, s, d) in enumerate(PROGRAM, start=1):
        while stack and stack[-1][1] <= s:
            stack.pop()
        e = {"name": name, "ts": s / 1e3, "dur": d / 1e3, "tid": 1,
             "args": {"bytes": 4096} if name == "query.fetch" else {}}
        if with_ids:
            e["id"] = i
            e["parent"] = stack[-1][0] if stack else None
        stack.append((i, s + d))
        events.append(e)
    return events


class _Ctx:
    def __init__(self, ring):
        self.spans = ring


@pytest.mark.parametrize("name, want", [
    ("query_prep_ms_per_batch.query", (5 + 6) / 2),
    ("d2h_copy_ms_per_batch.query", 3 / 2),
    ("rerank_ms_per_batch.query", (4 + 8) / 2),
])
def test_the_span_readers_read_ms_per_search_batch(name, want):
    assert bench_run._reader(name)(_Ctx(_ring())) == pytest.approx(want)


@pytest.mark.parametrize("ring", [
    [], _ring(with_ids=False),
    [e for e in _ring() if not e["name"].startswith("query.")]],
    ids=["empty", "no-ids", "no-query-spans"])
def test_the_span_readers_return_nothing_where_no_span_ran(ring):
    for name in NEW_METRICS:
        assert bench_run._reader(name)(_Ctx(ring)) is None


def test_a_profiler_trace_holds_the_query_spans_inside_the_service_span(
        tmp_path):
    """On the CPU: the program's spans reach a real profiler trace, with
    the five query spans inside each ``serve.search_batch``, in order."""
    import jax
    import numpy as np

    from repro import obs
    from repro.serve import SketchSearchService

    svc = SketchSearchService(m=32, seed=7, keep_host_oracle=False)
    rng = np.random.default_rng(3)
    keys = np.arange(60)
    for t in range(4):
        svc.ingest(f"t{t}", keys, rng.normal(size=60))
    queries = [(keys, rng.normal(size=60)) for _ in range(3)]
    svc.search_batch(queries, top_k=2, min_join=5, micro_batch=4)  # warm
    was = obs.enabled()
    jax.profiler.start_trace(str(tmp_path))
    obs.enable()
    try:
        svc.search_batch(queries, top_k=2, min_join=5, micro_batch=4)
    finally:
        if not was:
            obs.disable()
        jax.profiler.stop_trace()
    program = spans.program_spans(tracing.find_xplane(str(tmp_path)))
    (outer,) = [s for s in program if s[0] == "serve.search_batch"]
    inside = [s for s in program if outer[1] <= s[1]
              and s[1] + s[2] <= outer[1] + outer[2]]
    assert [s[0] for s in inside if s[0].startswith("query.")] == [
        "query.prep", "query.dispatch", "query.wait", "query.fetch",
        "query.rerank"]
    assert any(s[0].startswith("ops.") for s in inside)


def test_a_tiny_traced_run_reports_the_span_metrics():
    cell = chipbench_tiny.tiny_cell("w1_query_open")
    bench = json.loads((bench_run.ROOT / "BENCHMARK.json").read_text())
    cell.per_layer = cell.per_layer + [
        copy.deepcopy(m) for m in bench["per_layer"]
        if m["name"] in NEW_METRICS]
    res = bench_run.run(cell, 2 ** 31 + 5, 3.0, True, require_chip=False,
                        cache=False, log=lambda msg: None)
    assert res["correct"] is True
    for name in NEW_METRICS:
        got = res["metrics"][name]
        assert got["unit"] == "ms/batch" and got["value"] > 0


def test_the_chip_trace_with_spans_reduces_to_its_numbers():
    assert pathlib.Path(NEW).stat().st_size < 1 << 20
    trace, program, ring = spans.load(str(NEW))
    expect = chipbench_tiny.load_json(
        FIXTURES / "w1_query_open.spans.expect.json")
    assert trace.window_s() == pytest.approx(expect["window_s"])
    assert trace.busy_s() == pytest.approx(expect["busy_s"])
    ctx = _Ctx(ring)
    for name in NEW_METRICS:
        assert bench_run._reader(name)(ctx) == pytest.approx(
            expect["metrics"][name])
    idle = spans.idle_by_span(trace, program)
    assert sum(idle.values()) == pytest.approx(
        trace.window_s() - trace.busy_s(), abs=1e-9)
    assert sum(idle.values()) == pytest.approx(
        sum(trace.idle_by_label().values()), abs=1e-9)
    assert {k for k in idle if k.startswith("query.")} >= {
        "query.prep", "query.fetch", "query.rerank"}
    for name, want in expect["idle_gaps"].items():
        assert idle.get(name, 0.0) == pytest.approx(want)
