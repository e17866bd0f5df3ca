"""Tiny cells of the chip benchmark for CPU tests: the real configurations
and traffic files, cut to a lake and a window that the Pallas interpreter
runs in seconds."""
from __future__ import annotations

import copy
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import run as bench_run  # noqa: E402

TINY = {
    "w1_query_open": {"tables": 600, "rows_cap": 256, "rate_per_s": 12,
                      "planted_share": 0.5, "warm_nnz": [64, 192],
                      "query_cap": 192},
    "wdi_query_open": {"tables": 96, "rate_per_s": 8, "planted_share": 0.5,
                       "economies": 12, "years": 8},
    "w1_ingest": {"base_tables": 160, "pool_tables": 96, "rows_cap": 256,
                  "query_cap": 192, "ingest_batch": 32},
}


# the cells as a benchmark file would list them, so that the tests do not
# depend on which cells BENCHMARK.json holds
BENCH = {
    "configs": [
        {"name": "w1_opendata_icws",
         "file": "chipbench/configs/w1_opendata_icws.json"},
        {"name": "wdi_dense_jl", "file": "chipbench/configs/wdi_dense_jl.json"},
    ],
    "workloads": [
        {"name": "w1_query_open", "config": "w1_opendata_icws",
         "traffic": "w1_open", "chips": 1},
        {"name": "wdi_query_open", "config": "wdi_dense_jl",
         "traffic": "wdi_open", "chips": 1},
        {"name": "w1_ingest", "config": "w1_opendata_icws",
         "traffic": "w1_stream", "chips": 1},
    ],
    "end_to_end": [
        {"name": "query_p50_ms", "unit": "ms",
         "workloads": ["w1_query_open", "wdi_query_open"]},
        {"name": "ingest_rows_per_s", "unit": "rows/s",
         "workloads": ["w1_ingest"]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [
        {"name": "icws_scan_roofline", "unit": "%", "moves": "query_p50_ms",
         "workloads": ["w1_query_open"]},
        {"name": "latency_p99_ms.query", "unit": "ms",
         "moves": "query_p50_ms",
         "workloads": ["w1_query_open", "wdi_query_open"]},
        {"name": "device_idle_share.query", "unit": "%",
         "moves": "query_p50_ms",
         "workloads": ["w1_query_open", "wdi_query_open"]},
        {"name": "device_idle_share.ingest", "unit": "%",
         "moves": "ingest_rows_per_s", "workloads": ["w1_ingest"]},
    ],
}


def tiny_cell(name: str) -> "bench_run.Cell":
    cell = copy.deepcopy(bench_run.load_cell(name, BENCH))
    t = TINY[name]
    cfg, traffic = cell.config, cell.traffic
    cfg["tables"] = t.get("tables", cfg["tables"])
    lake = cfg["lake"]
    if "rows_cap" in t:
        lake["rows_cap"] = t["rows_cap"]
    if "economies" in t:
        lake["economies"], lake["years"] = t["economies"], t["years"]
        E, Y = t["economies"], t["years"]
        lo = lake["cover_min"]
        traffic["warm_nnz"] = [int(-(-lo * E // 1) * -(-lo * Y // 1)),
                               E * Y]
    if "query_cap" in t:
        traffic["queries"]["rows_cap"] = t["query_cap"]
    for k in ("rate_per_s", "planted_share", "warm_nnz", "base_tables",
              "pool_tables"):
        if k in t:
            traffic[k] = t[k]
    if "ingest_batch" in t:
        cfg["serving"]["ingest_batch"] = t["ingest_batch"]
    cell.spec = dict(cell.spec, control_requests=4)
    return cell


def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())
