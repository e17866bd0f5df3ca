"""The least work of a corpus scan, whatever kernel implements it.

One micro-batch needs each of the three corpus fields read once, at the
store's resident bytes per row (``describe()["bytes_per_row"]``), plus the
micro-batch's query sketches.  Block sizes, grids, re-reads and the output
planes are implementation choices and are not counted.  Linear sketches
also need their dot products: 2 * pairs * Q * P * W operations.
"""
from __future__ import annotations

import json
import pathlib

FIELDS = 3          # key indicator, values, squared values
PAIRS = 6           # field pairs the correlation needs

_PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(kind: str) -> dict:
    table = json.loads(_PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; the table "
                       f"holds {sorted(table)}")
    return table[kind]


def scan_bytes(corpus_rows: int, bytes_per_row: float, queries: int,
               query_row_bytes: float) -> float:
    return FIELDS * (corpus_rows * bytes_per_row + queries * query_row_bytes)


def scan_flops(corpus_rows: int, queries: int, width: int) -> float:
    return 2.0 * PAIRS * queries * corpus_rows * width


def least_seconds(nbytes: float, flops: float, peak: dict):
    """(seconds, bound): the larger of bytes over HBM bandwidth and
    operations over the bf16 peak, and which of the two it is."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_flop = flops / peak["bf16_flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_flop else (t_flop, "bf16_flops")
