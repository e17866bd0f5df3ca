"""Serving front-end for corpus-scale dataset search.

Wraps :class:`repro.data.DatasetSearchIndex` in the shape a query service
needs: named-table ingestion, ``search`` / ``search_batch`` endpoints, and
request accounting.  The hot loop is the device path -- the corpus lives in
the index's canonical field-stacked :class:`repro.data.CorpusStore` (one
device-resident copy, amortized in-place append), and every query, single
or batched, is one ``[3Q, N]`` ICWS sketch launch plus ONE fused
multi-field many-vs-many estimate launch off those buffers (``search`` is
the Q=1 case; ``search_batch`` amortizes launches across a micro-batch,
which is why batched serving is the high-traffic endpoint).  Pass a
``mesh`` with a multi-device corpus axis to serve the estimate launch
sharded over corpus rows -- rankings are bitwise identical to the
single-device path.  All of it is independent of how the corpus was
ingested.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs as _obs
from repro.data import DatasetSearchIndex, SearchResult
from repro.obs.metrics import Histogram


class ServiceStats:
    """Request accounting: a thin compatibility view over latency histograms.

    Historically this was a dataclass of running sums; the fields the old
    mean-only API exposed (``queries_served``, ``total_query_ms``,
    ``last_query_ms``, ...) are now properties derived from three private
    :class:`repro.obs.metrics.Histogram` instances, which additionally give
    the service exact-window p50/p95/p99 for :meth:`SketchSearchService.
    describe`.  The histograms are owned by this object (not the global
    obs registry), so they always record -- two services in one process
    never share latency state -- and they work with observability disabled.
    """

    def __init__(self) -> None:
        self.tables_ingested = 0
        self.rows_ingested = 0
        # batched-endpoint query count (micro-batches land in batch_hist)
        self.batch_queries_served = 0
        self.query_hist = Histogram("serve.query_seconds")
        self.batch_hist = Histogram("serve.batch_seconds")
        # per-query latency through the batched endpoint: one observation
        # per micro-batch (batch wall time / batch size)
        self.batched_query_hist = Histogram("serve.batched_query_seconds")

    # -- compatibility view (the pre-histogram field set) -------------------
    @property
    def queries_served(self) -> int:
        return self.query_hist.count

    @property
    def total_query_ms(self) -> float:
        return self.query_hist.sum * 1e3

    @property
    def last_query_ms(self) -> float:
        return self.query_hist.last * 1e3

    @property
    def batches_served(self) -> int:
        return self.batch_hist.count

    @property
    def total_batch_ms(self) -> float:
        return self.batch_hist.sum * 1e3

    @property
    def last_batch_ms(self) -> float:
        return self.batch_hist.last * 1e3

    @property
    def mean_query_ms(self) -> float:
        return self.total_query_ms / max(self.queries_served, 1)

    @property
    def mean_batch_ms(self) -> float:
        return self.total_batch_ms / max(self.batches_served, 1)

    @property
    def mean_batched_query_ms(self) -> float:
        """Per-query latency through the batched endpoint."""
        return self.total_batch_ms / max(self.batch_queries_served, 1)


class SketchSearchService:
    """Sketch-index serving: ingest tables once, answer joinability/corr
    queries against the whole corpus from sketches alone."""

    def __init__(self, m: int = 256, seed: int = 0,
                 backend: str = "device", keep_host_oracle: bool = True,
                 mesh=None, family: str = "icws", packed: bool = False,
                 audit_every: int = 0):
        # family picks the device serving sketch (any repro.data
        # .FAMILY_NAMES entry -- icws/dmh/cs/jl/ts/ps today), sized
        # storage-matched from m (see repro.data.families) -- the same
        # corpus can be served under any family for an apples-to-apples
        # error/throughput comparison.  packed=True keeps the corpus in the
        # family's bit-packed wire layout (roughly half the resident bytes
        # per row) and serves through the unpack-in-kernel estimate twins.
        self.index = DatasetSearchIndex(m=m, seed=seed, backend=backend,
                                        keep_host_oracle=keep_host_oracle,
                                        mesh=mesh, family=family,
                                        packed=packed)
        self.stats = ServiceStats()
        # per-tenant latency histograms (private, always recording)
        self._tenant_hists: Dict[str, Histogram] = {}
        # estimator-quality audit: with observability enabled and
        # audit_every=N > 0, every Nth single search re-scores its top hit
        # against the host oracle and feeds quality.ppm_error (ICWS device
        # indexes that kept the oracle only; a no-op otherwise)
        self.audit_every = int(audit_every)

    # -- ingestion ----------------------------------------------------------
    def ingest(self, name: str, keys: np.ndarray, values: np.ndarray, *,
               tenant: Optional[str] = None) -> None:
        """Ingest one named table; ``tenant`` scopes it to a logical corpus
        inside the shared arena (see :meth:`search`).  Duplicate-name
        checks are scoped per tenant -- tenants are logical corpora, so two
        tenants may each own a table called "sales"."""
        if any(t.name == name
               for t in self._tenant_tables_or_empty(tenant)):
            raise ValueError(f"table {name!r} already ingested"
                             + (f" for tenant {tenant!r}"
                                if tenant is not None else ""))
        with _obs.span("serve.ingest", table=name, tenant=tenant):
            self.index.add_table(name, keys, values, tenant=tenant)
        self.stats.tables_ingested += 1
        self.stats.rows_ingested += len(keys)
        if _obs.enabled():
            _obs.counter("serve.tables_ingested_total").inc()
            _obs.counter("serve.rows_ingested_total").inc(len(keys))

    def _tenant_tables_or_empty(self, tenant: Optional[str]):
        """The tenant's tables for the duplicate-name check -- empty for a
        tenant that has not ingested yet (a KeyError here would make the
        FIRST ingest of every tenant fail)."""
        if tenant is not None and str(tenant) not in self.index.tenants():
            return []
        return self.index._tenant_table_list(tenant)

    def ingest_many(self, tables: Sequence[Tuple[str, np.ndarray, np.ndarray]],
                    *, tenant: Optional[str] = None) -> None:
        for name, keys, values in tables:
            self.ingest(name, keys, values, tenant=tenant)

    def ingest_many_sharded(self,
                            tables: Sequence[Tuple[str, np.ndarray,
                                                   np.ndarray]],
                            *, shards: int,
                            tenant: Optional[str] = None) -> None:
        """Ingest a batch of tables via a ``shards``-way parallel lake build
        (:meth:`repro.data.DatasetSearchIndex.add_tables_sharded`)."""
        tables = list(tables)
        seen = {t.name for t in self._tenant_tables_or_empty(tenant)}
        for name, _, _ in tables:
            if name in seen:
                raise ValueError(f"table {name!r} already ingested"
                                 + (f" for tenant {tenant!r}"
                                    if tenant is not None else ""))
            seen.add(name)
        with _obs.span("serve.ingest_sharded", shards=shards, tenant=tenant,
                       tables=len(tables)):
            self.index.add_tables_sharded(tables, shards=shards,
                                          tenant=tenant)
        self.stats.tables_ingested += len(tables)
        rows = sum(len(k) for _, k, _ in tables)
        self.stats.rows_ingested += rows
        if _obs.enabled():
            _obs.counter("serve.tables_ingested_total").inc(len(tables))
            _obs.counter("serve.rows_ingested_total").inc(rows)

    # -- queries ------------------------------------------------------------
    def search(self, keys: np.ndarray, values: np.ndarray, *,
               top_k: int = 10, min_join: float = 1.0,
               backend: Optional[str] = None,
               tenant: Optional[str] = None) -> List[SearchResult]:
        """Rank tables by |corr|; ``tenant`` searches one logical corpus of
        the shared arena, bitwise equal to a dedicated single-tenant index
        over the same tables."""
        t0 = time.perf_counter()
        with _obs.span("serve.search", tenant=tenant,
                       family=self.index.family.name,
                       backend=backend or self.index.backend):
            results = self.index.query(keys, values, top_k=top_k,
                                       min_join=min_join, backend=backend,
                                       tenant=tenant)
        dt = time.perf_counter() - t0
        self.stats.query_hist.record(dt)
        self._record_request("search", dt, tenant)
        if self.audit_every:
            self._maybe_audit(keys, values, results, top_k, min_join,
                              backend, tenant)
        return results

    # -- telemetry helpers --------------------------------------------------
    def _record_request(self, endpoint: str, dt: float,
                        tenant: Optional[str]) -> None:
        if tenant is not None:
            hist = self._tenant_hists.get(str(tenant))
            if hist is None:
                hist = Histogram("serve.tenant_seconds",
                                 {"tenant": str(tenant)})
                self._tenant_hists[str(tenant)] = hist
            hist.record(dt)
        if not _obs.enabled():
            return
        _obs.histogram("serve.request_seconds", endpoint=endpoint).record(dt)
        if endpoint == "search":
            _obs.counter("serve.queries_total").inc()
        if tenant is not None:
            _obs.histogram("serve.tenant_request_seconds",
                           tenant=str(tenant)).record(dt)

    def _maybe_audit(self, keys, values, results, top_k, min_join,
                     backend, tenant) -> None:
        """Every ``audit_every``-th search, re-score against the host oracle
        and feed the rolling quality.ppm_error gauge (see repro.obs.quality).

        Only meaningful for ICWS device indexes that kept the oracle at
        ingest; anything else (other families, host backend, empty results)
        silently skips -- auditability is a property of the index, and the
        quality channel must never change what the endpoint returns.
        """
        if not _obs.enabled() or not results:
            return
        if (backend or self.index.backend) != "device":
            return
        if self.index.family.name != "icws" or not self.index.keep_host_oracle:
            return
        if self.stats.queries_served % self.audit_every != 0:
            return
        ref = self.index.query(keys, values, top_k=top_k, min_join=min_join,
                               backend="host", tenant=tenant)
        ref_by_name = {r.name: r for r in ref}
        for r in results:
            mate = ref_by_name.get(r.name)
            if mate is None or mate.join_size == 0:
                continue
            _obs.record_sample(self.index.family.name, r.join_size,
                               mate.join_size)

    _EMPTY_QUERY = (np.zeros(0, np.int64), np.zeros(0, np.float64))

    def search_batch(self, queries: Sequence[Tuple[np.ndarray, np.ndarray]],
                     *, top_k: int = 10, min_join: float = 1.0,
                     backend: Optional[str] = None, micro_batch: int = 16,
                     tenant: Optional[str] = None
                     ) -> List[List[SearchResult]]:
        """Batched search: Q ``(keys, values)`` queries, Q result lists.

        Queries run through :meth:`DatasetSearchIndex.query_batch` in
        micro-batches of ``micro_batch``; on the device backend the tail
        micro-batch is padded with empty queries so every launch sees the
        same ``[micro_batch]`` batch shape and reuses one jit/kernel cache
        entry (empty padding sketches to the ``fp == -1`` sentinel, estimates
        to zero, and is dropped before results are returned).  Results are
        identical to a loop of :meth:`search`; per-batch latency lands in
        ``stats.last_batch_ms`` / ``stats.mean_batched_query_ms``.
        """
        if micro_batch < 1:
            raise ValueError("micro_batch must be >= 1")
        queries = list(queries)
        resolved = backend or self.index.backend
        results: List[List[SearchResult]] = []
        for lo in range(0, len(queries), micro_batch):
            chunk = queries[lo:lo + micro_batch]
            t0 = time.perf_counter()
            if resolved == "device" and len(chunk) < micro_batch:
                padded = chunk + [self._EMPTY_QUERY] * (micro_batch - len(chunk))
            else:
                padded = chunk
            with _obs.span("serve.search_batch", tenant=tenant,
                           family=self.index.family.name,
                           batch=len(chunk)):
                out = self.index.query_batch(padded, top_k=top_k,
                                             min_join=min_join,
                                             backend=backend, tenant=tenant)
            results.extend(out[:len(chunk)])
            dt = time.perf_counter() - t0
            self.stats.batch_hist.record(dt)
            self.stats.batched_query_hist.record(dt / len(chunk))
            self.stats.batch_queries_served += len(chunk)
            self._record_request("search_batch", dt, tenant)
            if _obs.enabled():
                _obs.counter("serve.batches_total").inc()
                _obs.counter("serve.batch_queries_total").inc(len(chunk))
        return results

    def describe(self, tenant: Optional[str] = None) -> Dict[str, object]:
        """Service accounting.  With ``tenant``, the report scopes to that
        logical corpus: its table count, rows, row ranges in the arena, and
        its share of the storage-doubles ledger."""
        store = self.index.store
        if tenant is not None:
            tables = self.index._tenant_table_list(tenant)
            if store is not None:
                acct = store.describe_tenants()[str(tenant)]
                rows, ranges = acct["rows"], acct["ranges"]
                storage = acct["storage_doubles"]
            else:
                rows, ranges = float(len(tables)), 1.0
                storage = float(len(tables) * 3
                                * self.index.family.storage_doubles_per_row())
            report = {
                "tenant": tenant,
                "family": self.index.family.name,
                "backend": self.index.backend,
                "tables": len(tables),
                "corpus_rows": rows,
                "row_ranges": ranges,
                "storage_doubles": storage,
            }
            hist = self._tenant_hists.get(str(tenant))
            if hist is not None and hist.count:
                report.update(_latency_fields("request_ms", hist))
            return report
        # a host-only index (backend="host") has no device store, but its
        # corpus is just as real -- one row per ingested table per field.
        # Report the table-derived row count rather than a misleading 0;
        # host corpora are exact-size, so capacity == rows there.
        rows = int(store.size if store is not None
                   else len(self.index.tables))
        cap = int(store.capacity if store is not None
                  else len(self.index.tables))
        report = {
            "family": self.index.family.name,
            "backend": self.index.backend,
            "packed": bool(store.packed) if store is not None else False,
            "bytes_per_row": float(store.bytes_per_row()
                                   if store is not None else 0),
            "tables": len(self.index.tables),
            "tenants": len(self.index.tenants()),
            "storage_doubles": self.index.storage_doubles(),
            "corpus_rows": rows,
            "corpus_capacity": cap,
            "queries_served": self.stats.queries_served,
            "mean_query_ms": self.stats.mean_query_ms,
            "batches_served": self.stats.batches_served,
            "batch_queries_served": self.stats.batch_queries_served,
            "mean_batch_ms": self.stats.mean_batch_ms,
            "mean_batched_query_ms": self.stats.mean_batched_query_ms,
        }
        report.update(_latency_fields("query_ms", self.stats.query_hist))
        report.update(_latency_fields("batch_ms", self.stats.batch_hist))
        report.update(_latency_fields("batched_query_ms",
                                      self.stats.batched_query_hist))
        return report


def _latency_fields(prefix: str, hist: Histogram) -> Dict[str, float]:
    """p50/p95/p99 (ms) of one latency histogram, keyed ``<prefix>_p50``..."""
    return {
        prefix + "_p50": hist.quantile(0.50) * 1e3,
        prefix + "_p95": hist.quantile(0.95) * 1e3,
        prefix + "_p99": hist.quantile(0.99) * 1e3,
    }
