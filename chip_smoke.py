#!/usr/bin/env python3
"""Bring-up smoke of the dataset-search service on a TPU.

Drives :class:`repro.serve.SketchSearchService` end to end through its
public endpoints (``ingest_many_sharded``, ``search_batch``, ``search``) on
one chip, at a lake size its users would call real: the open-data
join-correlation deployment (Santos et al., *Correlation Sketches*, SIGMOD
2021; the World Bank study of the paper's §6).  2^18 (key, value) tables
with heavy-tailed row counts (16-4096) over shared hot key domains (days,
zip codes, borough IDs) plus a cold entity domain, values at realistic
levels (log-uniform over five decades, spread 1-50% of the level), ICWS
m=256 (the service default), packed, no host oracle.  A few joinable,
correlated tables are planted per query.  Everything is generated from ``--seed``.

Checks (each failure exits non-zero):

* every planted table is in its query's top-10;
* the served join sizes of the planted tables are within 0.05 of the
  exact float64 numpy join, in the paper's normalized error
  ``|est - exact| / (|a| |b|)`` over the key-multiplicity vectors;
* device estimates equal ``repro.core.ICWS.estimate_batch`` on the same
  sketches, read back from the store, to <= 1e-5 relative;
* one micro-batch equals a loop of ``search``, bitwise.

Then a reduced lake is served once under each of the other five sketch
families, so that every family's kernels run compiled.

``--chips 4`` runs only the sharded path and its comparison: the same lake
sharded over four chips, rankings bitwise equal to the single-device index
on device 0, and every store buffer spread over the four devices.

Run from the repository root:  python3 chip_smoke.py [--seed N] [--chips 4]
The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.core.icws import ICWS, StackedICWS  # noqa: E402
from repro.serve import SketchSearchService  # noqa: E402

M = 256                  # the service's default ICWS width
LAKE_TABLES = 2 ** 18
SMALL_TABLES = 2 ** 14   # the reduced lake of the other families
N_QUERIES = 32           # two micro-batches
MICRO_BATCH = 16
TOP_K, MIN_JOIN = 10, 30
INGEST_BATCH = 4096      # tables per ingest_many_sharded call
PLANTED = 3              # joinable, correlated tables per query
QUERY_DAYS = 730         # a query table: two years of daily values
JOIN_TOL = 0.05          # normalized join error |est - exact| / (|a| |b|)
PARITY_TOL = 1e-5        # device vs host estimator, relative
OTHER_FAMILIES = ("dmh", "cs", "jl", "ts", "ps")

# shared key domains: (name, distinct keys, share of lake tables)
DOMAINS = (("day", 7300, 0.40), ("zip", 42_000, 0.30),
           ("borough", 5, 0.05), ("entity", 1 << 24, 0.25))
DAY = 0
CONTIGUOUS = (0, 1)      # day and zip tables cover a range of keys


def _key_offset(domain: int) -> int:
    return domain << 26            # disjoint domains, keys < 2^31


@dataclasses.dataclass
class Lake:
    """Tables as slices of two flat arrays, and the queries."""
    names: list
    starts: np.ndarray             # [T + 1] row offsets into keys/values
    keys: np.ndarray               # int64 [rows]
    values: np.ndarray             # float64 [rows]
    queries: list                  # [(keys, values)]
    planted: list                  # per query: names of its planted tables

    def table(self, i: int):
        lo, hi = self.starts[i], self.starts[i + 1]
        return self.names[i], self.keys[lo:hi], self.values[lo:hi]

    def __len__(self) -> int:
        return len(self.names)


def make_lake(seed: int, n_tables: int, n_queries: int) -> Lake:
    """The W1 lake, generated in bulk.  A background table draws its keys
    from one domain: day- and zip-keyed tables cover a contiguous range
    (a time series, a region) with 1-3 rows per key, borough and entity
    tables draw keys at random; values are independent of everything.
    Values sit at the levels of open-data indicators, not around zero: a
    table's level is log-uniform over five decades and its spread 1-50%
    of the level.  Each query is a daily series over a two-year window at
    a level of 10^2-10^5 with a 5% spread (independent daily draws, so two
    queries' series do not correlate by chance the way random walks do),
    and its planted tables repeat the window's days 1-3 times with values
    at their own level that follow the query's series."""
    rng = np.random.default_rng(seed)
    n_bg = n_tables - n_queries * PLANTED
    sizes = np.array([d[1] for d in DOMAINS])
    share = np.array([d[2] for d in DOMAINS])
    dom = rng.choice(len(DOMAINS), size=n_bg, p=share / share.sum())
    rows = np.minimum(np.floor(16 * rng.random(n_bg) ** (-1 / 1.2)),
                      4096).astype(np.int64)
    per_key = rng.choice([1, 1, 1, 2, 3], size=n_bg)
    span = -(-rows // per_key)
    start = (rng.random(n_bg) * np.maximum(sizes[dom] - span, 1)).astype(
        np.int64)
    total = int(rows.sum())
    dom_rows = np.repeat(dom, rows)
    within = np.arange(total) - np.repeat(np.cumsum(rows) - rows, rows)
    ranged = np.repeat(start, rows) + within // np.repeat(per_key, rows)
    drawn = (rng.random(total) * sizes[dom_rows]).astype(np.int64)
    contiguous = np.isin(dom_rows, CONTIGUOUS)
    keys = (np.where(contiguous, ranged, drawn)
            + (dom_rows.astype(np.int64) << 26))
    level = np.repeat(10.0 ** rng.uniform(0, 5, n_bg), rows)
    spread = np.repeat(rng.uniform(0.01, 0.5, n_bg), rows)
    values = level * (1.0 + spread * rng.normal(size=total))
    names = [f"t{i:07d}" for i in range(n_bg)]
    parts_k, parts_v, lens = [keys], [values], [rows]

    queries, planted = [], []
    for q in range(n_queries):
        d0 = int(rng.integers(0, DOMAINS[DAY][1] - QUERY_DAYS))
        days = _key_offset(DAY) + d0 + np.arange(QUERY_DAYS, dtype=np.int64)
        signal = rng.normal(size=QUERY_DAYS)
        queries.append((days, 10.0 ** rng.uniform(2, 5)
                        * (1.0 + 0.05 * signal)))
        mine = []
        for j in range(PLANTED):
            rep = j + 1
            slope = (1.0, -2.0, 0.5)[j]
            k = np.tile(days, rep)
            v = 10.0 ** rng.uniform(1, 4) * (1.0 + 0.05 * (
                slope * np.tile(signal, rep) + 0.1 * rng.normal(size=k.size)))
            parts_k.append(k)
            parts_v.append(v)
            lens.append(np.array([k.size]))
            mine.append(f"planted_q{q:02d}_{j}")
        names.extend(mine)
        planted.append(mine)
    lens = np.concatenate(lens)
    starts = np.concatenate([[0], np.cumsum(lens)])
    return Lake(names=names, starts=starts, keys=np.concatenate(parts_k),
                values=np.concatenate(parts_v), queries=queries,
                planted=planted)


def ingest(svc: SketchSearchService, lake: Lake, *, on_batch=None) -> dict:
    """Ingest the lake through ``ingest_many_sharded(batch, shards=1)``,
    one sketch launch per batch, tables sorted by row count so that one
    long table does not pad a whole batch.  Returns name -> lake index."""
    order = np.argsort(np.diff(lake.starts), kind="stable")
    for lo in range(0, len(order), INGEST_BATCH):
        batch = [lake.table(int(i)) for i in order[lo:lo + INGEST_BATCH]]
        svc.ingest_many_sharded(batch, shards=1)
        if on_batch is not None:
            on_batch(svc)
    return {lake.names[int(i)]: int(i) for i in order}


def serve(svc: SketchSearchService, lake: Lake):
    """All queries through ``search_batch`` in micro-batches."""
    return svc.search_batch(lake.queries, top_k=TOP_K, min_join=MIN_JOIN,
                            micro_batch=MICRO_BATCH)


def check_recall(lake: Lake, results) -> int:
    """Planted tables missing from their query's top-10."""
    return sum(name not in {r.name for r in res}
               for res, mine in zip(results, lake.planted) for name in mine)


def _counts(keys):
    u, c = np.unique(np.asarray(keys, np.int64), return_counts=True)
    return u, c.astype(np.float64)


def exact_join(qkeys, tkeys) -> tuple:
    """Exact join size of two key columns (joined row pairs, float64) and
    the norms of their multiplicity vectors (the paper's error scale)."""
    uq, cq = _counts(qkeys)
    ut, ct = _counts(tkeys)
    _, iq, it = np.intersect1d(uq, ut, return_indices=True)
    return (float(np.dot(cq[iq], ct[it])), float(np.linalg.norm(cq)),
            float(np.linalg.norm(ct)))


def check_join_error(lake: Lake, where: dict, results) -> float:
    """Largest normalized join error of the served planted tables, each
    against its own query (a full two-year overlap)."""
    worst = 0.0
    for (qkeys, _), res, mine in zip(lake.queries, results, lake.planted):
        for r in res:
            if r.name in mine:
                exact, na, nb = exact_join(qkeys,
                                           lake.table(where[r.name])[1])
                worst = max(worst, abs(r.join_size - exact) / (na * nb))
    return worst


def check_parity(svc: SketchSearchService, lake: Lake, results,
                 n_queries: int = 4) -> float:
    """Device vs host estimator on the same sketches: the served join sizes
    of the top-10 rows against ``ICWS.estimate_batch`` over the query's
    device sketch and the corpus rows read back from the store."""
    index = svc.index
    fam = index.family
    row_of = {t.name: i for i, t in enumerate(index.tables)}
    bufs = index.store.buffers()
    host = ICWS(m=fam.m, seed=fam.seed)
    worst = 0.0
    for (qkeys, qvals), res in list(zip(lake.queries, results))[:n_queries]:
        fp, val, norm, _ = (np.asarray(c) for c in
                            fam.sketch_rows(list(index.vectorize(qkeys,
                                                                 qvals))))
        rows = np.array([row_of[r.name] for r in res])
        cfp, cval, cnorm, _ = (np.asarray(c) for c in fam.unpack_rows(
            tuple(b[0, rows] for b in bufs)))      # key-indicator field
        k = rows.size
        a = StackedICWS(fingerprints=np.repeat(fp[:1], k, axis=0),
                        values=np.repeat(val[:1], k, axis=0).astype(np.float64),
                        norm=np.full(k, float(norm[0])))
        b = StackedICWS(fingerprints=cfp, values=cval.astype(np.float64),
                        norm=cnorm.astype(np.float64))
        want = host.estimate_batch(a, b)
        got = np.array([r.join_size for r in res])
        scale = np.maximum(np.maximum(np.abs(want), np.abs(got)), 1e-12)
        worst = max(worst, float(np.max(np.abs(got - want) / scale)))
    return worst


def check_batched_equals_sequential(svc: SketchSearchService,
                                    lake: Lake) -> bool:
    """One micro-batch through ``search_batch`` == a loop of ``search``."""
    qs = lake.queries[:MICRO_BATCH]
    batched = svc.search_batch(qs, top_k=TOP_K, min_join=MIN_JOIN,
                               micro_batch=MICRO_BATCH)
    return batched == [svc.search(k, v, top_k=TOP_K, min_join=MIN_JOIN)
                       for k, v in qs]


def serve_family(family: str, lake: Lake) -> tuple:
    """Serve a lake once under ``family``: (tables, planted recall,
    all fields finite)."""
    svc = SketchSearchService(m=M, family=family, packed=True,
                              keep_host_oracle=False)
    ingest(svc, lake)
    results = serve(svc, lake)
    finite = all(np.isfinite([r.join_size, r.sum_b, r.corr]).all()
                 for res in results for r in res)
    missing = check_recall(lake, results)
    n = len(lake.queries) * PLANTED
    return len(svc.index.tables), (n - missing) / n, finite


def _device_line(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def run_one_chip(seed: int, log) -> None:
    t0 = time.perf_counter()
    lake = make_lake(seed, LAKE_TABLES, N_QUERIES)
    log(f"lake: {len(lake)} tables, {lake.keys.size} rows, generated in "
        f"{time.perf_counter() - t0:.1f} s")

    svc = SketchSearchService(m=M, packed=True, keep_host_oracle=False)
    t0 = time.perf_counter()
    where = ingest(svc, lake)
    store = svc.index.store
    log(f"ingest: {store.size} tables resident (capacity {store.capacity}, "
        f"packed {store.bytes_per_row()} B/row/field, "
        f"{store.capacity * store.fields * store.bytes_per_row() / 2**30:.3f}"
        f" GiB) in {time.perf_counter() - t0:.1f} s (set-up, compiles "
        f"included)")
    _require(store.size == len(lake) >= LAKE_TABLES, "lake not resident")

    t0 = time.perf_counter()
    results = serve(svc, lake)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = serve(svc, lake)
    t_warm = time.perf_counter() - t0
    log(f"queries: {len(lake.queries)} in micro-batches of {MICRO_BATCH}: "
        f"{t_cold:.2f} s first pass (compiles included), {t_warm:.2f} s "
        f"warm (not a benchmark)")
    _require(again == results, "a repeated query pass changed the results")

    missing = check_recall(lake, results)
    log(f"recall: {len(lake.queries) * PLANTED - missing}/"
        f"{len(lake.queries) * PLANTED} planted tables in their top-{TOP_K}")
    _require(missing == 0, f"{missing} planted tables missed the top-{TOP_K}")

    err = check_join_error(lake, where, results)
    log(f"join error vs exact numpy: max normalized {err:.4f} "
        f"(tolerance {JOIN_TOL})")
    _require(err <= JOIN_TOL, "join sizes outside tolerance")

    rel = check_parity(svc, lake, results)
    log(f"device/host estimator parity: max rel {rel:.2e} "
        f"(tolerance {PARITY_TOL:.0e})")
    _require(rel <= PARITY_TOL, "device estimates diverge from the host")

    same = check_batched_equals_sequential(svc, lake)
    log(f"batched == sequential: {same}")
    _require(same, "search_batch differs from a loop of search")

    small = make_lake(seed + 1, SMALL_TABLES, MICRO_BATCH)
    for family in OTHER_FAMILIES:
        t0 = time.perf_counter()
        n, recall, finite = serve_family(family, small)
        log(f"family {family}: {n} tables served compiled, planted recall "
            f"{recall:.2f}, finite {finite}, {time.perf_counter() - t0:.1f} s")
        _require(finite, f"family {family} returned non-finite results")


def run_four_chips(seed: int, log) -> None:
    import jax
    from repro.launch.mesh import make_corpus_mesh

    mesh = make_corpus_mesh()
    lake = make_lake(seed, LAKE_TABLES, N_QUERIES)
    sharded = SketchSearchService(m=M, packed=True, keep_host_oracle=False,
                                  mesh=mesh)
    single = SketchSearchService(m=M, packed=True, keep_host_oracle=False)
    grown = []

    def placement(svc):
        store = svc.index.store
        cap = store.capacity
        for b in store.buffers():
            shards = b.addressable_shards
            _require(len({s.device for s in shards}) == mesh.size,
                     f"a store buffer is not spread over {mesh.size} devices")
            _require(all(s.data.shape[1] == cap // mesh.size for s in shards),
                     "a store shard does not hold capacity / devices rows")
        if not grown or grown[-1] != cap:
            grown.append(cap)

    t0 = time.perf_counter()
    ingest(sharded, lake, on_batch=placement)
    log(f"sharded ingest: {sharded.index.store.size} tables over "
        f"{mesh.size} devices, capacity grew {' -> '.join(map(str, grown))}"
        f", every buffer {mesh.size}-way after each growth, "
        f"{time.perf_counter() - t0:.1f} s")
    _require(len(grown) > 1, "the store never grew")
    t0 = time.perf_counter()
    ingest(single, lake)
    log(f"single-device ingest on {single.index.store.buffers()[0].devices()}"
        f": {time.perf_counter() - t0:.1f} s")

    a, b = serve(sharded, lake), serve(single, lake)
    log(f"sharded == single-device rankings, bitwise: {a == b}")
    _require(a == b, "sharded rankings differ from the single-device index")
    _require(check_recall(lake, a) == 0, "planted tables missed the top-10")
    log(f"peak_bytes_in_use per device: "
        f"{[_peak_bytes(d) for d in jax.devices()]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded corpus path and its "
                         "single-device comparison")
    args = ap.parse_args(argv)

    import jax
    from repro.compile_cache import enable_compile_cache
    from repro.kernels import ops

    devices = jax.devices()
    if devices[0].platform != "tpu" or ops._interpret():
        raise SystemExit(f"chip_smoke: no TPU (jax sees "
                         f"{devices[0].platform}); nothing was run")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices; jax sees {len(devices)}")
    cache = enable_compile_cache()

    def log(msg):
        print(msg, flush=True)

    log(f"device: {devices[0].device_kind} x{len(devices)}, compile cache "
        f"{cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(args.seed, log)
    else:
        run_one_chip(args.seed, log)
        log(f"peak_bytes_in_use: {_peak_bytes(devices[0])}")
    log(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": _device_line(devices)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
