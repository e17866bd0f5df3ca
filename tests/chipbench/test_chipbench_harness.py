"""The harness end to end on the CPU, with its look for a chip skipped: the
result line's keys, and a run without a TPU."""
import json
import os
import subprocess
import sys
import types

import pytest

import chipbench_tiny
from chipbench import mix
from chipbench import run as bench_run

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _run(name, *, trace=False, seconds=2.0, **kw):
    cell = chipbench_tiny.tiny_cell(name)
    return bench_run.run(cell, 2 ** 31 + 3, seconds, trace,
                         require_chip=False, cache=False,
                         log=lambda msg: None, **kw)


def test_result_line_carries_the_five_keys_and_the_checks_last():
    res = _run("w1_query_open")
    assert list(res) == KEYS + ["checks"]
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"query_p50_ms", "setup_s"}
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for v in res["checks"].values():
        assert set(v) == {"value", "limit"}
    json.dumps(res)


def test_traced_run_adds_the_device_window_and_breakdown():
    res = _run("w1_query_open", trace=True, seconds=3.0)
    assert list(res) == KEYS + ["breakdown", "checks"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["correct"] is True
    p99 = res["metrics"]["latency_p99_ms.query"]
    assert p99["unit"] == "ms" and p99["value"] > 0


def test_ingest_cell_reports_rows_per_second():
    res = _run("w1_ingest")
    assert set(res["metrics"]) == {"ingest_rows_per_s", "setup_s"}
    assert res["metrics"]["ingest_rows_per_s"]["value"] > 0
    assert res["correct"] is True


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(bench_run.HERE / "run.py"), "--workload",
         "w1_query_open", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=bench_run.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_sweep_reports_one_row_per_rate():
    from chipbench import sweep
    cell = chipbench_tiny.tiny_cell("w1_query_open")
    rows = list(sweep.sweep(cell, 5, 1.0, [4.0, 8.0], require_chip=False,
                            cache=False))
    assert [r["rate_per_s"] for r in rows] == [4.0, 8.0]
    assert all(r["requests"] > 0 and r["p99_ms"] >= r["p50_ms"]
               for r in rows)


KIND_API = ("build", "warm_up", "window", "checked", "lake_at_close",
            "readings")


@pytest.mark.parametrize("path", sorted(
    (bench_run.HERE / "traffic").glob("*.json")), ids=lambda p: p.stem)
def test_each_traffic_file_names_a_kind_module_found_by_name(path):
    kind = json.loads(path.read_text())["kind"]
    mod = mix.load(kind)
    assert all(callable(getattr(mod, f, None)) for f in KIND_API)


def test_an_unknown_traffic_kind_is_an_error():
    with pytest.raises(ValueError, match="unknown traffic kind"):
        mix.load("no_such_kind")


@pytest.mark.parametrize("traced_from, want", [
    (10.0, 199.0),          # every request answered before the stretch
    (1.0, 108.91),          # the first ten only
    (0.05, None),           # none
], ids=["all", "before-stretch", "none"])
def test_the_p99_reader_reads_requests_answered_before_the_stretch(
        traced_from, want):
    import numpy as np
    latency = np.concatenate([np.arange(100.0, 110.0),
                              np.full(90, 199.0)]) / 1e3
    done = np.concatenate([np.full(10, 0.5), np.full(90, 2.0)])
    win = mix.Window(attempted=100, failed=0, answers=[], latency_s=latency,
                     done_s=done)
    read = bench_run._reader("latency_p99_ms.query")
    got = read(types.SimpleNamespace(window=win, traced_from=traced_from))
    assert got == pytest.approx(want) if want is not None else got is None


def test_the_p99_reader_reads_nothing_in_a_window_without_latencies():
    win = mix.Window(attempted=4, failed=0, answers=[], rows_per_s=1e5)
    read = bench_run._reader("latency_p99_ms.query")
    assert read(types.SimpleNamespace(window=win, traced_from=1.0)) is None


def test_a_metric_without_a_file_of_its_own_reads_through_its_family():
    query = bench_run._reader("device_idle_share.query")
    ingest = bench_run._reader("device_idle_share.ingest")
    assert query.__code__.co_filename == ingest.__code__.co_filename
    with pytest.raises(FileNotFoundError):
        bench_run._reader("no_such_metric.query")
