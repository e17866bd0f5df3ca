"""The WDI cell served with the JL family (``wdi_jl_query_open``) on the
CPU: a tiny cut of it comes out correct under its limits, a broken service
and the control do not, the JL packed service serves every planted table
within its sketch's error, and the cell's new readers read the trace
recorded on the chip as they did there."""
import copy
import math
import types

import numpy as np
import pytest

import chipbench_tiny
from chipbench import control_readings, mix, reference, spans, tracing
from chipbench import run as bench_run
from repro.serve import SketchSearchService

CELL = "wdi_jl_query_open"
FIXTURES = bench_run.HERE / "fixtures"
NEW_READERS = ("jl_scan_roofline", "query_sketch_device_ms_per_batch.query",
               "query_sketch_pad_share.query")

# the cell as a benchmark file lists it, so that the tests do not depend on
# which cells BENCHMARK.json holds
BENCH = {
    "configs": [{"name": "wdi_dense_jl",
                 "file": "chipbench/configs/wdi_dense_jl.json"}],
    "workloads": [{"name": CELL, "config": "wdi_dense_jl",
                   "traffic": "wdi_jl_open", "chips": 1}],
    "end_to_end": [
        {"name": "query_p50_ms", "unit": "ms", "workloads": [CELL]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [
        {"name": n, "unit": u, "moves": "query_p50_ms", "workloads": [CELL]}
        for n, u in [("jl_scan_roofline", "%"),
                     ("query_sketch_device_ms_per_batch.query", "ms/batch"),
                     ("query_sketch_pad_share.query", "%"),
                     ("latency_p99_ms.query", "ms"),
                     ("device_idle_share.query", "%")]],
}
ECONOMIES, YEARS = 12, 8


def tiny_cell() -> "bench_run.Cell":
    """The cell cut to 12 economies x 8 years and 96 tables."""
    cell = copy.deepcopy(bench_run.load_cell(CELL, BENCH))
    cell.config["tables"] = 96
    lake = cell.config["lake"]
    lake["economies"], lake["years"] = ECONOMIES, YEARS
    lo = lake["cover_min"]
    cell.traffic.update(
        rate_per_s=8, planted_share=0.5,
        warm_nnz=[math.ceil(lo * ECONOMIES) * math.ceil(lo * YEARS),
                  ECONOMIES * YEARS])
    cell.spec = dict(cell.spec, control_requests=4)
    return cell


def _run(**kw):
    return bench_run.run(tiny_cell(), 2 ** 31 + 7, 2.0, False,
                         require_chip=False, cache=False,
                         log=lambda msg: None, **kw)


class AlteredJoin(SketchSearchService):
    """Every served join size scaled by 1.5 where it is produced."""

    def search_batch(self, queries, **kw):
        out = super().search_batch(queries, **kw)
        for res in out:
            for r in res:
                r.join_size *= 1.5
        return out


def test_the_cells_files_hold_the_deployment_as_published():
    cell = bench_run.load_cell(CELL, BENCH)
    assert cell.config["service"] == {"m": 256, "family": "jl",
                                      "packed": True,
                                      "keep_host_oracle": False}
    assert cell.config["reduced"] == [] and cell.config["tables"] == 2 ** 14
    assert cell.traffic["kind"] == "open"
    assert cell.traffic["shape_seed"] == 1204
    assert cell.traffic["planted_share"] == 0.0625
    assert cell.traffic["warm_nnz"] == [5109, 13888]
    assert len(cell.traffic["queries"]["planted"]["slopes"]) == 3
    assert cell.spec["control_requests"] == 32


def test_tiny_cut_is_correct_under_the_cells_limits():
    res = _run()
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"query_p50_ms", "setup_s"}
    assert res["checks"]["planted_missed"]["value"] == 0.0


@pytest.mark.parametrize("how", ["altered_join", "control"])
def test_broken_service_and_control_are_not_correct(how):
    kw = ({"service_cls": AlteredJoin} if how == "altered_join"
          else {"control": True})
    res = _run(**kw)
    assert res["correct"] is False
    assert [k for k, v in res["checks"].items() if v["value"] > v["limit"]]
    if how == "control":
        assert res["program"]["correct"] is True


def test_jl_packed_service_serves_every_planted_table_within_its_error():
    cell = tiny_cell()
    inputs = mix.build(cell.config, cell.traffic, 2 ** 33 + 1, 2.0)
    svc = SketchSearchService(**cell.config["service"])
    bench_run.ingest_lake(svc, inputs.lake, cell.serving["ingest_batch"])
    m = svc.index.family.m
    assert m == cell.config["sketch"]["width"]
    tol = 5.0 * math.sqrt(2.0 / m)
    asked = [i for i, p in enumerate(inputs.planted) if p]
    assert asked
    s = cell.serving
    out = svc.search_batch([inputs.queries[i] for i in asked],
                           top_k=s["top_k"], min_join=s["min_join"],
                           micro_batch=s["micro_batch"])
    for i, res in zip(asked, out):
        served = {r.name: r for r in res}
        assert set(inputs.planted[i]) <= set(served)
        hits = [served[n] for n in inputs.planted[i]]
        ex = reference.join_stats(inputs.queries[i],
                                  [inputs.lookup(n) for n in inputs.planted[i]])
        join = np.array([r.join_size for r in hits])
        sum_b = np.array([r.sum_b for r in hits])
        assert np.all(np.abs(join - ex.join) <= tol * ex.norm_a * ex.norm_b)
        assert np.all(np.abs(sum_b - ex.sum_b)
                      <= tol * ex.norm_a * ex.vnorm_b)


@pytest.mark.parametrize("dtype", [np.float64, reference.BF16],
                         ids=["f64", "bf16"])
@pytest.mark.parametrize("block", [1, 7, 1024])
def test_table_index_ranks_as_the_lake_index_bit_for_bit(dtype, block):
    cell = tiny_cell()
    inputs = mix.build(cell.config, cell.traffic, 2 ** 34 + 3, 2.0)
    lake_index = reference.LakeIndex(inputs.lake)
    table_index = control_readings.TableIndex(inputs.lake, block=block)
    keys, values = inputs.queries[0]
    stray = (np.r_[keys, 2 ** 40], np.r_[values, 1.0])   # a key no table has
    for q in inputs.queries + [stray]:
        assert (table_index.rank(q, 10, 1.0, dtype)
                == lake_index.rank(q, 10, 1.0, dtype))


def test_control_readings_read_what_run_py_control_reads():
    got = control_readings.readings(tiny_cell(), 2 ** 31 + 7, 2.0)
    res = _run(control=True)
    assert res["failed"] == 0
    assert got["control"] == {"correct": res["correct"],
                              "checks": res["checks"]}
    assert got["control"]["correct"] is False
    assert got["planted_bf16"]["correct"] is False
    assert got["planted_bf16"]["checks"]["planted_missed"]["value"] == 0.0


# -- the new readers ---------------------------------------------------------
MS = 1_000_000                      # ns


def _ctx(trace, ring=()):
    from chipbench import roofline
    return types.SimpleNamespace(
        trace=trace, spans=list(ring), log=lambda msg: None,
        describe={"corpus_rows": 16384, "bytes_per_row": 770.0},
        service={"m": 256, "micro_batch": 16, "width": 385},
        peaks=roofline.peaks("TPU v5 lite"))


def _hand_trace(kernel: str) -> tracing.Trace:
    """Two search_batch calls in a 100 ms window, each running a 2 ms
    query sketch launch and a 1 ms scan."""
    host = [[tracing.WINDOW, 0, 100 * MS],
            ["bench.search_batch", 10 * MS, 30 * MS],
            ["bench.search_batch", 60 * MS, 30 * MS]]
    ops = []
    for start in (15 * MS, 65 * MS):
        ops.append([0, f"%{kernel}.3", "", start, 2 * MS])
        ops.append([0, "%linear_estimate_fields_pallas.1", "",
                    start + 2 * MS, MS])
    return tracing.Trace(ops=ops, modules=[], host=host, devices=1)


@pytest.mark.parametrize("kernel, want", [
    ("jl_sketch_pallas", 2.0), ("icws_sketch_pallas", None)])
def test_query_sketch_device_time_per_call_on_a_hand_trace(kernel, want):
    read = bench_run._reader("query_sketch_device_ms_per_batch.query")
    got = read(_ctx(_hand_trace(kernel)))
    assert got == pytest.approx(want) if want is not None else got is None


def _dispatch(rows, width, nnz):
    return {"name": "query.dispatch", "ts": 0.0, "dur": 1.0,
            "args": {"rows": rows, "width": width, "nnz": nnz}}


@pytest.mark.parametrize("ring, want", [
    ([_dispatch(48, 13824, 48 * 13824)], 0.0),
    ([_dispatch(48, 10240, 245760), _dispatch(48, 5120, 122880)],
     100.0 * (1 - 368640 / (48 * 15360))),
    ([{"name": "query.dispatch", "ts": 0.0, "dur": 1.0, "args": {}}], None),
    ([], None),
], ids=["full", "half", "no-attributes", "no-spans"])
def test_pad_share_sums_the_dispatch_spans_lanes(ring, want):
    got = bench_run._reader("query_sketch_pad_share.query")(
        _ctx(_hand_trace("jl_sketch_pallas"), ring))
    assert got == pytest.approx(want) if want is not None else got is None


def test_recorded_chip_trace_reads_the_new_metrics():
    path = FIXTURES / f"{CELL}.trace.json.gz"
    assert path.stat().st_size < 1 << 20
    trace, _program, ring = spans.load(str(path))
    expect = chipbench_tiny.load_json(FIXTURES / f"{CELL}.expect.json")
    ctx = _ctx(trace, ring)
    ctx.describe = expect["describe"]
    assert trace.window_s() == pytest.approx(expect["window_s"])
    assert trace.busy_s() == pytest.approx(expect["busy_s"])
    assert set(expect["metrics"]) == set(NEW_READERS)
    for name, value in expect["metrics"].items():
        assert bench_run._reader(name)(ctx) == pytest.approx(value)
