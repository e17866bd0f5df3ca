"""Roofline-driven block-size autotuner for the estimate/sketch kernels.

The serving kernels (`repro.kernels.estimate` / `sample_estimate` /
`icws_sketch`) launch with hand-picked ``bq/bp/bm/bw/bn/br`` defaults.
This module searches that space analytically -- no device timing loop --
using the same two inputs the repo already maintains:

  * the per-kernel BlockSpec block-I/O accounting behind the PB001/PB002
    static budget rule (``python -m repro.analysis --budget-report``, the
    ``vmem-budget-report`` CI artifact): the tuner reproduces that
    accounting per candidate and rejects anything over the 2 MiB budget,
    and the CLI cross-checks tuned entries against a report file when one
    is passed via ``--report``;
  * the roofline cost terms (:mod:`repro.roofline.terms`): per candidate,
    ``time = max(hbm_bytes / HBM_BW, flops / PEAK_FLOPS) + grid_steps *
    step_overhead(backend)``.  On real TPUs the bandwidth term dominates;
    under the Pallas interpreter (cpu backend -- CI and every dev box)
    each grid step re-enters python, so the per-step overhead term does,
    and fewer/larger blocks win whenever they fit the budget.

Tuned entries persist in a JSON cache (default ``block_cache.json`` next
to this file, override via ``$REPRO_BLOCK_CACHE``) keyed by kernel group,
backend, and the kernel's *reduction* dims.  That keying is a correctness
decision, not a convenience: the repo pins bitwise ranking identities
(batched == sequential, sharded == single-device, tenant == dedicated,
packed == unpacked-roundtripped), and those hold only if every launch
that is compared bitwise reduces in the same block order.  Reduction dims
(``bm``/``bt``/``bu``/``bw``) therefore depend only on the sketch width
-- identical across batch sizes, shards, and tenants, and shared between
a kernel and its packed twin (widths normalized to even).  Row-tile dims
(``bq``/``bp``/``br``) never affect per-element results (padding is
sliced off), so :func:`resolve` clamps them down for small launches
without breaking anything.

Set ``REPRO_AUTOTUNE_DISABLE=1`` to force the hand-picked defaults.
Regenerate the committed cache with::

    PYTHONPATH=src python -m repro.analysis --budget-report report.json
    PYTHONPATH=src python -m repro.roofline.autotune --backend cpu \
        --report report.json

This module stays stdlib-only (like the rest of ``repro.roofline`` and
``repro.analysis``) so tooling can import it without jax.
"""
from __future__ import annotations

import argparse
import functools
import itertools
import json
import os
import pathlib
from typing import Dict, Iterable, Mapping, Optional, Sequence, Tuple

from .terms import HBM_BW, PEAK_FLOPS

# Mirrors repro.analysis.config.AnalysisConfig.vmem_block_budget (PB001).
VMEM_BLOCK_BUDGET = 2 * 1024 * 1024
# Cap on kernel-internal temporaries the BlockSpec accounting cannot see
# (the sample kernel's [S, bp] match tiles, the sketch kernel's ~6
# per-lane intermediates) so tuning never trades grid steps for an
# interpreter-hostile VMEM blowup.
INTERMEDIATE_BUDGET = 3 * 1024 * 1024
_BYTES_PER_ELEM = 4

CACHE_ENV = "REPRO_BLOCK_CACHE"
DISABLE_ENV = "REPRO_AUTOTUNE_DISABLE"
DEFAULT_CACHE = pathlib.Path(__file__).with_name("block_cache.json")

# Per-grid-step launch overhead (s).  TPU: sequential-grid bookkeeping.
# Everything else runs the Pallas interpreter, where each step is a python
# round-trip -- large enough that minimizing grid steps is the whole game.
_STEP_OVERHEAD = {"tpu": 2e-6}
_DEFAULT_STEP_OVERHEAD = 5e-4


def _ceil_div(n: int, d: int) -> int:
    return -(-int(n) // int(d))


def _ceil_to(n: int, base: int) -> int:
    return base * _ceil_div(max(int(n), 1), base)


def _even(n: int) -> int:
    return int(n) + (int(n) % 2)


# ---------------------------------------------------------------------------
# Kernel models: one entry per kernel *group*.  A group covers a kernel and
# its packed twin (same grid geometry, the packed corpus block is strictly
# smaller, so the unpacked accounting below is the shared upper bound).
# ``key_dims`` are the reduction dims that form the cache key; ``dims`` is
# the full tuning shape.  ``report_kernel`` names the group's unpacked
# pallas_call in the --budget-report artifact.
# ---------------------------------------------------------------------------
KERNELS: Dict[str, Dict] = {
    "estimate_fields": {
        "report_kernel": "estimate_fields_pallas",
        "dims": ("G", "Q", "P", "m"),
        "key_dims": ("m",),
        "defaults": {"bq": 16, "bp": 512, "bm": 256},
        "candidates": {"bq": (8, 16, 32, 64), "bp": (128, 256, 512, 1024),
                       "bm": (128, 256, 512)},
        # resolve-time clamping of row dims: block -> (shape dim, tile base)
        "row_dims": {"bq": ("Q", 8), "bp": ("P", 128)},
        "flops_per_lane": 8.0,
    },
    "linear_estimate_fields": {
        "report_kernel": "linear_estimate_fields_pallas",
        "dims": ("G", "R", "Q", "P", "W"),
        "key_dims": ("W",),
        "defaults": {"bq": 8, "bp": 128, "bw": 128},
        "candidates": {"bq": (8, 16, 32, 64), "bp": (128, 256, 512, 1024),
                       "bw": (128, 256, 512)},
        "row_dims": {"bq": ("Q", 8), "bp": ("P", 128)},
        "flops_per_lane": 2.0,
    },
    "sample_estimate_fields": {
        # whole sample rows ride in every block (a full array dim) with a
        # block of SAMPLE_BQ queries; only the corpus row tile is free.
        # The accounting below bounds the slot width by SAMPLE_S_CAP.
        "report_kernel": "sample_estimate_fields_pallas",
        "dims": ("G", "Q", "P", "S"),
        "key_dims": ("S",),
        "defaults": {"bp": 128},
        "candidates": {"bp": (128, 256, 512)},
        "row_dims": {"bp": ("P", 128)},
        "flops_per_lane": 6.0,
    },
    "icws_sketch": {
        "report_kernel": "icws_sketch_pallas",
        "dims": ("B", "m", "N"),
        "key_dims": ("m", "N"),
        "defaults": {"br": 1, "bm": 128, "bn": 256},
        "candidates": {"br": (1, 2, 4, 8), "bm": (128, 256),
                       "bn": (256, 512)},
        "row_dims": {"br": ("B", 1)},
        "flops_per_lane": 30.0,
    },
    "dmh_sketch": {
        # the bin-state width bm is NOT tuned: it is the lane-rounded
        # sketch width (a capacity the ops wrapper derives from m), so the
        # accounting below bounds it by DMH_BM_CAP, the largest serving m
        # rounded to lanes.  Only (br, bn) are free.
        "report_kernel": "dmh_sketch_pallas",
        "dims": ("B", "m", "N"),
        "key_dims": ("m", "N"),
        "defaults": {"br": 1, "bn": 256},
        "candidates": {"br": (1, 2, 4, 8), "bn": (256, 512, 1024)},
        "row_dims": {"br": ("B", 1)},
        "flops_per_lane": 6.0,
    },
}

# Upper bound on the DMH kernel's VMEM-resident bin-state width: the
# largest sketch width any serving path launches (storage budget 400 ->
# m = 266) rounded up to a lane multiple.  Used for the PB001/PB002-style
# block accounting of ``dmh_sketch`` entries, where the real bm <= this.
DMH_BM_CAP = 384
# Upper bound on the sample kernel's full-dim slot blocks: the slot width
# of the largest serving budget (storage 400 -> 399 slots, lane-rounded);
# and its query block (sample_estimate_fields_pallas's bq).
SAMPLE_S_CAP = 512
SAMPLE_BQ = 8


def _block_shapes(kernel: str, b: Mapping[str, int]) -> list:
    """(count, block shape) per BlockSpec, mirroring the pallas_call specs
    the PB001 rule sums (4 bytes/elem).  Packed twins reuse the group's
    accounting as an upper bound."""
    if kernel == "estimate_fields":
        return [(2, (1, b["bq"], b["bm"])), (2, (1, b["bp"], b["bm"])),
                (2, (1, b["bq"], b["bp"]))]
    if kernel == "linear_estimate_fields":
        return [(1, (1, b["bq"], 1, b["bw"])), (1, (1, b["bp"], 1, b["bw"])),
                (1, (1, 1, b["bq"], b["bp"]))]
    if kernel == "sample_estimate_fields":
        # 3 query planes [S, bq], 2 corpus planes [bp, S], taus, output
        return [(3, (1, 1, SAMPLE_S_CAP, SAMPLE_BQ)),
                (2, (1, b["bp"], SAMPLE_S_CAP)), (1, (1, 1, b["bp"])),
                (1, (1, SAMPLE_BQ, b["bp"]))]
    if kernel == "icws_sketch":
        # 3 inputs [br, bn]; 4 outputs + the pack_vals variant's 5th [br, bm]
        return [(3, (b["br"], b["bn"])), (5, (b["br"], b["bm"]))]
    if kernel == "dmh_sketch":
        # 3 inputs [br, bn]; 4 outputs + pack_vals' 5th at the bm cap
        return [(3, (b["br"], b["bn"])), (5, (b["br"], DMH_BM_CAP))]
    raise KeyError(f"unknown kernel group {kernel!r}")


def block_bytes(kernel: str, blocks: Mapping[str, int]) -> int:
    total = 0
    for count, shape in _block_shapes(kernel, blocks):
        n = 1
        for d in shape:
            n *= int(d)
        total += count * n * _BYTES_PER_ELEM
    return total


def _intermediate_bytes(kernel: str, b: Mapping[str, int]) -> int:
    if kernel == "sample_estimate_fields":
        # ~6 [S, bp] tiles: the transposed corpus planes, the
        # probabilities, and the per-slot match/term temporaries
        return 6 * _BYTES_PER_ELEM * SAMPLE_S_CAP * b["bp"]
    if kernel == "icws_sketch":
        # ~6 f32 [br, bm, bn] temporaries (5 uniform draws + hash math)
        return 6 * _BYTES_PER_ELEM * b["br"] * b["bm"] * b["bn"]
    if kernel == "dmh_sketch":
        # gather-based payload selection keeps the [br, bm, bn] cross
        # tensors down to ~2 (the bin-match mask and its argmin companion);
        # the per-lane variates are [br, bn] and the probe epilogue chunks
        # at [br, bm, 128] -- both dominated by the cross terms at any bn
        return 2 * _BYTES_PER_ELEM * b["br"] * DMH_BM_CAP * b["bn"]
    return 0


def _grid_steps(kernel: str, s: Mapping[str, int], b: Mapping[str, int]) -> int:
    if kernel == "estimate_fields":
        return (s["G"] * _ceil_div(s["Q"], b["bq"]) *
                _ceil_div(s["P"], b["bp"]) * _ceil_div(s["m"], b["bm"]))
    if kernel == "linear_estimate_fields":
        return (s["G"] * s["R"] * _ceil_div(s["Q"], b["bq"]) *
                _ceil_div(s["P"], b["bp"]) * _ceil_div(s["W"], b["bw"]))
    if kernel == "sample_estimate_fields":
        return (s["G"] * _ceil_div(s["P"], b["bp"]) *
                _ceil_div(s["Q"], SAMPLE_BQ))
    if kernel == "icws_sketch":
        return (_ceil_div(s["B"], b["br"]) * _ceil_div(s["m"], b["bm"]) *
                _ceil_div(s["N"], b["bn"]))
    if kernel == "dmh_sketch":
        # no m grid axis: the whole bin state stays VMEM-resident
        return _ceil_div(s["B"], b["br"]) * _ceil_div(s["N"], b["bn"])
    raise KeyError(f"unknown kernel group {kernel!r}")


def _lanes(kernel: str, s: Mapping[str, int], b: Mapping[str, int]) -> int:
    """Padded elementwise lanes actually computed -- charges block choices
    for the padding waste of oversized tiles."""
    if kernel == "estimate_fields":
        return (s["G"] * _ceil_to(s["Q"], b["bq"]) *
                _ceil_to(s["P"], b["bp"]) * _ceil_to(s["m"], b["bm"]))
    if kernel == "linear_estimate_fields":
        return (s["G"] * s["R"] * _ceil_to(s["Q"], b["bq"]) *
                _ceil_to(s["P"], b["bp"]) * _ceil_to(s["W"], b["bw"]))
    if kernel == "sample_estimate_fields":
        # every query slot meets every corpus slot of every row
        return s["G"] * s["Q"] * _ceil_to(s["P"], b["bp"]) * s["S"] * s["S"]
    if kernel == "icws_sketch":
        return (_ceil_to(s["B"], b["br"]) * _ceil_to(s["m"], b["bm"]) *
                _ceil_to(s["N"], b["bn"]))
    if kernel == "dmh_sketch":
        return _ceil_to(s["B"], b["br"]) * _ceil_to(s["N"], b["bn"])
    raise KeyError(f"unknown kernel group {kernel!r}")


def model_time_s(kernel: str, shape: Mapping[str, int],
                 blocks: Mapping[str, int], backend: str) -> float:
    """Roofline estimate for one launch: bandwidth/compute max plus the
    per-grid-step overhead of the backend."""
    steps = _grid_steps(kernel, shape, blocks)
    hbm = float(steps * block_bytes(kernel, blocks))
    flops = float(_lanes(kernel, shape, blocks)) * \
        KERNELS[kernel]["flops_per_lane"]
    compute = max(hbm / HBM_BW, flops / PEAK_FLOPS)
    return compute + steps * _STEP_OVERHEAD.get(backend,
                                                _DEFAULT_STEP_OVERHEAD)


def cache_key(kernel: str, backend: str, key: Mapping[str, int]) -> str:
    dims = KERNELS[kernel]["key_dims"]
    missing = [d for d in dims if d not in key]
    if missing:
        raise KeyError(f"{kernel} cache key needs dims {dims}; "
                       f"missing {missing}")
    # even-normalized so a kernel and its packed twin (width rounded up to
    # even at pack time) resolve the same entry -> same reduction blocks
    parts = ",".join(f"{d}={_even(key[d])}" for d in dims)
    return f"{kernel}|{backend}|{parts}"


def tune(kernel: str, shape: Mapping[str, int], backend: str, *,
         budget: int = VMEM_BLOCK_BUDGET,
         intermediate_budget: int = INTERMEDIATE_BUDGET) -> Dict:
    """Exhaustively score the candidate grid for one (kernel, shape,
    backend) and return a cache entry for the best block choice."""
    spec = KERNELS[kernel]
    missing = [d for d in spec["dims"] if d not in shape]
    if missing:
        raise KeyError(f"{kernel} tuning shape needs dims {spec['dims']}; "
                       f"missing {missing}")
    shape = {d: int(shape[d]) for d in spec["dims"]}
    names = tuple(spec["candidates"])
    best = None
    for combo in itertools.product(*(spec["candidates"][n] for n in names)):
        blocks = dict(zip(names, combo))
        bb = block_bytes(kernel, blocks)
        if bb > budget:
            continue
        if _intermediate_bytes(kernel, blocks) > intermediate_budget:
            continue
        t = model_time_s(kernel, shape, blocks, backend)
        steps = _grid_steps(kernel, shape, blocks)
        rank = (t, steps, bb, tuple(blocks[n] for n in names))
        if best is None or rank < best[0]:
            best = (rank, blocks, bb, steps, t)
    if best is None:
        raise ValueError(f"no {kernel} candidate fits the {budget}-byte "
                         f"block budget")
    _, blocks, bb, steps, t = best
    defaults = dict(spec["defaults"])
    default_t = model_time_s(kernel, shape, defaults, backend)
    if t > default_t:
        # Every feasible candidate models slower than the defaults (this
        # happens when the defaults themselves sit outside the candidate
        # budgets).  The defaults are what an uncached launch
        # runs anyway, so cache *them*: the entry stays self-consistent
        # (model.time_s == model.default_time_s) instead of pinning a
        # strictly worse-modeled block set.
        blocks = defaults
        bb = block_bytes(kernel, blocks)
        steps, t = _grid_steps(kernel, shape, blocks), default_t
    return {
        "kernel": kernel,
        "backend": backend,
        "key": {d: _even(shape[d]) for d in spec["key_dims"]},
        "blocks": blocks,
        "block_shapes": [[c, list(s)] for c, s in
                         _block_shapes(kernel, blocks)],
        "block_bytes": bb,
        "budget_bytes": budget,
        "shape": shape,
        "model": {
            "grid_steps": steps,
            "time_s": t,
            "default_grid_steps": _grid_steps(kernel, shape, defaults),
            "default_time_s": default_t,
        },
    }


# ---------------------------------------------------------------------------
# Cache I/O + launch-time resolution
# ---------------------------------------------------------------------------
def cache_path(path: Optional[os.PathLike] = None) -> pathlib.Path:
    if path is not None:
        return pathlib.Path(path)
    env = os.environ.get(CACHE_ENV)
    return pathlib.Path(env) if env else DEFAULT_CACHE


@functools.lru_cache(maxsize=8)
def _load_cache_cached(path_str: str, mtime_ns: int) -> Dict[str, Dict]:
    with open(path_str, "r", encoding="utf-8") as f:
        data = json.load(f)
    out = {}
    for entry in data.get("entries", []):
        out[cache_key(entry["kernel"], entry["backend"], entry["key"])] = entry
    return out


def load_cache(path: Optional[os.PathLike] = None) -> Dict[str, Dict]:
    """Cache entries keyed by :func:`cache_key`; {} when no cache file."""
    p = cache_path(path)
    try:
        stat = p.stat()
    except OSError:
        return {}
    return _load_cache_cached(str(p), stat.st_mtime_ns)


def save_cache(entries: Iterable[Dict],
               path: Optional[os.PathLike] = None) -> pathlib.Path:
    """Merge entries into the cache file (same key replaces) and rewrite it
    deterministically (sorted keys) so the committed artifact diffs clean."""
    p = cache_path(path)
    merged = dict(load_cache(p))
    for entry in entries:
        merged[cache_key(entry["kernel"], entry["backend"],
                         entry["key"])] = entry
    payload = {"version": 1,
               "entries": [merged[k] for k in sorted(merged)]}
    p.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n",
                 encoding="utf-8")
    _load_cache_cached.cache_clear()
    return p


def resolve(kernel: str, backend: str, key: Mapping[str, int], *,
            clamp: Optional[Mapping[str, Tuple[int, int]]] = None,
            path: Optional[os.PathLike] = None) -> Dict[str, int]:
    """Block kwargs for one launch, or {} to mean "use the defaults".

    ``key`` holds the kernel's reduction dims (see ``KERNELS[...]
    ["key_dims"]``).  ``clamp`` maps row-dim block names to ``(dim_size,
    tile_base)``: a tuned row block is cut down to the launch's padded row
    count so cache entries tuned at corpus scale never slow small test
    launches -- row dims are sliced-off padding, so this cannot change any
    per-element result.  Reduction dims are returned exactly as tuned.
    """
    if os.environ.get(DISABLE_ENV):
        return {}
    entry = load_cache(path).get(cache_key(kernel, backend, key))
    if not entry:
        return {}
    blocks = {k: int(v) for k, v in entry["blocks"].items()}
    for name, (dim, base) in (clamp or {}).items():
        if name in blocks:
            blocks[name] = min(blocks[name], _ceil_to(dim, base))
    return blocks


def clear_resolve_cache() -> None:
    """Test hook: drop the mtime-keyed cache-file memoization."""
    _load_cache_cached.cache_clear()


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def _parse_shape(text: str) -> Dict[str, int]:
    out = {}
    for part in text.split(","):
        name, _, val = part.partition("=")
        if not val:
            raise argparse.ArgumentTypeError(
                f"shape must be dim=int[,dim=int...]; got {text!r}")
        out[name.strip()] = int(val)
    return out


# Default tuning shapes: the perf_sketch.py serving geometries at the
# sketch widths the repo actually launches (dataset-search m, bench m).
_DEFAULT_SHAPES = {
    "estimate_fields": ({"G": 6, "Q": 16, "P": 4096, "m": 64},
                        {"G": 6, "Q": 16, "P": 4096, "m": 128},
                        {"G": 6, "Q": 16, "P": 4096, "m": 256}),
    "linear_estimate_fields": ({"G": 6, "R": 5, "Q": 16, "P": 4096,
                                "W": 128},),
    "sample_estimate_fields": ({"G": 6, "Q": 16, "P": 4096, "S": 100},
                               {"G": 6, "Q": 16, "P": 4096, "S": 400}),
    "icws_sketch": ({"B": 48, "m": 128, "N": 256},
                    {"B": 48, "m": 256, "N": 256},
                    {"B": 48, "m": 64, "N": 4096}),
    "dmh_sketch": ({"B": 48, "m": 64, "N": 4096},
                   {"B": 48, "m": 128, "N": 256},
                   {"B": 48, "m": 256, "N": 256},
                   {"B": 16, "m": 66, "N": 1024},
                   {"B": 16, "m": 266, "N": 1024}),
}


def _check_report(entries: Sequence[Dict], report_path: str) -> list:
    """Cross-check tuned entries against a --budget-report artifact: the
    report must know the group's kernel, and the tuned block bytes must fit
    the report's budget.  Returns human-readable problem strings."""
    with open(report_path, "r", encoding="utf-8") as f:
        report = json.load(f)
    rows = report if isinstance(report, list) else report.get("report", [])
    by_kernel = {r.get("kernel"): r for r in rows}
    problems = []
    for entry in entries:
        rk = KERNELS[entry["kernel"]]["report_kernel"]
        row = by_kernel.get(rk)
        if row is None:
            problems.append(f"{entry['kernel']}: kernel {rk!r} not in "
                            f"budget report {report_path}")
            continue
        budget = int(row.get("budget_bytes", VMEM_BLOCK_BUDGET))
        if entry["block_bytes"] > budget:
            problems.append(
                f"{entry['kernel']}: tuned blocks {entry['block_bytes']}B "
                f"exceed report budget {budget}B")
    return problems


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.roofline.autotune",
        description="Tune Pallas block sizes from the roofline model and "
                    "persist them to the block cache.")
    parser.add_argument("--kernel", action="append", choices=sorted(KERNELS),
                        help="kernel group to tune (repeatable; default all)")
    parser.add_argument("--shape", action="append", type=_parse_shape,
                        help="tuning shape as dim=int,... (repeatable; "
                             "requires exactly one --kernel)")
    parser.add_argument("--backend", default="cpu",
                        help="jax backend the entries are for (default cpu)")
    parser.add_argument("--report",
                        help="vmem-budget-report JSON to cross-check against "
                             "(from python -m repro.analysis --budget-report)")
    parser.add_argument("--out", help="cache file to update "
                                      f"(default {DEFAULT_CACHE})")
    parser.add_argument("--dry-run", action="store_true",
                        help="print entries without writing the cache")
    args = parser.parse_args(argv)

    kernels = args.kernel or sorted(KERNELS)
    if args.shape and len(kernels) != 1:
        parser.error("--shape requires exactly one --kernel")
    entries = []
    for kernel in kernels:
        shapes = args.shape or _DEFAULT_SHAPES[kernel]
        for shape in shapes:
            entries.append(tune(kernel, shape, args.backend))
    if args.report:
        problems = _check_report(entries, args.report)
        if problems:
            for p in problems:
                print(f"autotune: {p}")
            return 1
    for entry in entries:
        model = entry["model"]
        print(f"{cache_key(entry['kernel'], entry['backend'], entry['key'])}"
              f": {entry['blocks']} "
              f"steps {model['default_grid_steps']} -> {model['grid_steps']}"
              f" ({entry['block_bytes']}B of {entry['budget_bytes']}B)")
    if not args.dry_run:
        path = save_cache(entries, args.out)
        print(f"wrote {len(entries)} entries -> {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
