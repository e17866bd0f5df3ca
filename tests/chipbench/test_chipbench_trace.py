"""The trace -> metrics reduction: on a hand-made trace whose numbers are
known, and on the small trace recorded on the chip and committed as a
fixture."""
import pathlib
import types

import pytest

import chipbench_tiny
from chipbench import roofline, tracing
from chipbench import run as bench_run

MS = 1_000_000                      # ns

FIXTURE = bench_run.HERE / "fixtures" / "w1_query_open.trace.json.gz"


def _hand_trace(split: int = 1) -> tracing.Trace:
    """A 100 ms window with two search_batch calls of 30 ms, each running
    a 10 ms scan (cut into ``split`` launches) and a 2 ms top-k module;
    an arrival wait fills the gap between them."""
    host = [[tracing.WINDOW, 0, 100 * MS],
            ["bench.search_batch", 10 * MS, 30 * MS],
            ["bench.await_arrival", 40 * MS, 20 * MS],
            ["bench.search_batch", 60 * MS, 30 * MS],
            ["bench.search_batch", 95 * MS, 30 * MS]]   # runs past the end
    ops, modules = [], []
    for start in (20 * MS, 70 * MS, 100 * MS):
        step = 10 * MS // split
        for k in range(split):
            ops.append([0, "%estimate_fields_pallas.1", "",
                        start + k * step, step])
        ops.append([0, "%sort.1", "", start + 10 * MS, 2 * MS])
        modules.append([0, "jit_top_k(7)", start + 10 * MS, 2 * MS])
    return tracing.Trace(ops=ops, modules=modules, host=host, devices=1)


def test_busy_idle_and_per_call_on_a_hand_trace():
    t = _hand_trace()
    assert t.window_s() == pytest.approx(0.1)
    assert t.busy_s() == pytest.approx(0.024)           # 2 x (10 + 2) ms
    assert t.per_call("bench.search_batch", r"^%estimate_fields_pallas") == (
        pytest.approx(0.020), 2)
    secs, calls = t.per_call("bench.search_batch", r"jit_top_k",
                             modules=True)
    assert (secs, calls) == (pytest.approx(0.004), 2)
    idle = t.idle_by_label()
    assert sum(idle.values()) == pytest.approx(0.076)
    assert idle == {"bench.search_batch": pytest.approx(0.041),
                    "bench.await_arrival": pytest.approx(0.020),
                    "host_other": pytest.approx(0.015)}
    b = t.breakdown()
    assert b["device_ops"][0] == ["%estimate_fields_pallas.1",
                                  pytest.approx(0.020)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def _ctx(trace):
    return types.SimpleNamespace(
        trace=trace, spans=[], log=lambda msg: None, ingest_batch=4096,
        describe={"corpus_rows": 262144, "bytes_per_row": 1540.0},
        service={"m": 256, "micro_batch": 16, "width": 385},
        peaks=roofline.peaks("TPU v5 lite"))


def test_scan_least_bytes_count_each_field_once():
    # three fields of 2^18 packed ICWS rows, plus 16 query sketches
    assert roofline.scan_bytes(262144, 1540, 16, 8 * 256) == (
        3 * (262144 * 1540 + 16 * 2048))


@pytest.mark.parametrize("split", [1, 2, 12])
def test_scan_roofline_does_not_depend_on_the_kernels_grid(split):
    """The same work read as one launch per micro-batch or as twelve (one
    per field pair and query block) gives the same share."""
    read = bench_run._reader("icws_scan_roofline")
    share = read(_ctx(_hand_trace(split)))
    least = 3 * (262144 * 1540 + 16 * 2048) / 819e9
    assert share == pytest.approx(100 * least / 0.010)


def test_readers_return_nothing_where_nothing_ran():
    t = _hand_trace()
    assert bench_run._reader("jl_scan_roofline")(_ctx(t)) is None
    assert bench_run._reader(
        "sketch_device_ms_per_ktable.ingest")(_ctx(t)) is None
    assert bench_run._reader("ingest_host_ms_per_ktable")(_ctx(t)) is None


def test_ingest_host_self_time_leaves_out_build_and_append():
    us = 1000.0
    spans = [
        {"name": "serve.ingest_sharded", "ts": 0.0, "dur": 100 * us,
         "tid": 1, "args": {"tables": 4096}},
        {"name": "merge.build_sharded", "ts": 40 * us, "dur": 30 * us,
         "tid": 1, "args": {}},
        {"name": "store.append", "ts": 60 * us, "dur": 5 * us,
         "tid": 1, "args": {}},                  # inside the build
        {"name": "store.append", "ts": 75 * us, "dur": 10 * us,
         "tid": 1, "args": {}},
    ]
    ctx = _ctx(_hand_trace())
    ctx.spans = spans
    got = bench_run._reader("ingest_host_ms_per_ktable")(ctx)
    assert got == pytest.approx((100 - 30 - 10) / 4.096)


def test_recorded_chip_trace_reduces_to_its_numbers():
    t = tracing.Trace.load(str(FIXTURE))
    assert pathlib.Path(FIXTURE).stat().st_size < 1 << 20
    expect = chipbench_tiny.load_json(
        bench_run.HERE / "fixtures" / "w1_query_open.expect.json")
    ctx = _ctx(t)
    assert t.window_s() == pytest.approx(expect["window_s"])
    assert t.busy_s() == pytest.approx(expect["busy_s"])
    for name, value in expect["metrics"].items():
        assert bench_run._reader(name)(ctx) == pytest.approx(value)
