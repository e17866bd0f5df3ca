"""Dataset-search service: the paper's motivating application (Section 1.3).

Tables are (key column, value column) pairs.  Per table we pre-compute WMH
sketches of the four vector representations from Figure 3:

    x^{1[K]}   key multiplicities (1 per row)   -> join sizes (inner products)
    x^{V}      values summed at key index       -> post-join SUM / MEAN / corr
    x^{V^2}    squared values summed at key     -> post-join variance

Repeated join keys are aggregated (values summed, multiplicities counted), so
real-world tables with duplicate keys ingest cleanly and join sizes count
joined row *pairs*, as SQL join cardinality does.

Serving path (default, ``backend="device"``): all three field corpora live
in ONE canonical :class:`~repro.data.store.CorpusStore` -- field-stacked
``[3, capacity, m]`` device buffers with amortized in-place append (the
single device-resident copy of the corpus; there is no per-field duplicate
and no stack-for-batching duplicate).  Every device query, single or
batched, is sketched by one ``[3Q, N]`` ICWS kernel launch and answered by
ONE fused multi-field many-vs-many estimate launch
(:func:`repro.kernels.ops.icws_estimate_fields`) straight off the store
buffers; a single query is simply the Q=1 case.  Candidate selection (the
``CANDIDATES`` best tables by evidence-weighted |sketch-estimated corr|
among sufficiently-joinable ones) happens in jnp before any result leaves
the device; the host then refines the correlation of just those
candidates from the matched KMV samples, in one vectorized pass, and keeps
the top-k.  Only the candidates' scores, indices, join and sum estimates
are copied to the host (``Q * k * 16`` bytes a call), never a ``[Q, P]``
plane.

The value and squared-value fields are sketched over values centred on
their table's mean, rounded to a multiple of the largest power of two at
or below their standard deviation (so integer data stays integer and
shard sums stay exact).  Correlation is shift-invariant, and the
five-estimate correlation cancels catastrophically when a table's level
dwarfs its spread (``join * sum(v^2) - sum(v)^2`` of two nearly equal,
noisy estimates); centred fields keep that difference at the size of the
spread.  Served sums add the shift back: ``sum_b = <1[K_A], V_B - c_B> +
c_B * |K_A join K_B|``.

Sharded serving: construct the index with a ``mesh`` whose corpus axis (see
:func:`repro.distributed.sharding.corpus_axis`, logical axis ``"corpus"``,
by default the ``data`` mesh axis) spans 2+ devices, and the fused estimate
launch runs per shard over corpus rows under ``repro.compat.shard_map``
with queries replicated, followed by a per-shard top-k and a global merge.
Rankings are bitwise identical to the single-device path: per-row estimate
math is independent of the row count, and both top-k paths break score
ties by ascending index (:func:`repro.kernels.ops.top_k`).

Oracle path (``backend="host"``): the original host-numpy WMH implementation,
kept verbatim as the cross-checked reference for the device path.  Every §1.3
statistic falls out of inner-product estimates:

    |K_A join K_B|      = <1[K_A], 1[K_B]>
    SUM(V_A after join) = <x^{V_A}, 1[K_B]>
    MEAN(V_A)           = SUM / join_size
    corr(V_A, V_B)      via the five inner products (Santos et al. 2021).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import KMV, SparseVec, WeightedMinHash, stack_wmh
from repro.core.kmv import KMVSketch
from repro.core.wmh import StackedWMH, WMHSketch
from repro import obs as _obs
from repro.kernels import ops

from .families import FAMILY_NAMES, make_family, wmh_storage
from .ingest import padded_width
from .merge import build_sharded
from .store import CorpusStore

FIELDS = ("key_indicator", "values", "values_sq")

# Field-pair maps for the fused multi-field estimate kernel, in
# _corr_scores argument order (join, sum_a, sum_b, sum_a2, sum_b2, prod):
# estimate g pairs query field QFIELD[g] with corpus field CFIELD[g].
_IND, _VAL, _SQ = 0, 1, 2
QFIELD = (_IND, _VAL, _IND, _SQ, _IND, _VAL)
CFIELD = (_IND, _IND, _VAL, _IND, _SQ, _VAL)
# the estimates the host re-rank reads, gathered at the candidates on the
# device: join (0) and sum_b (2), in the same order
CANDIDATE_PLANES = (0, 2)

# Candidates per query the device hands the host to re-rank by sample
# correlation (at least top_k).  The device score only preselects, so a
# truly correlated table may sit a few places below the top-k there; the
# host pass costs time linear in the candidates.
CANDIDATES = 32

# KMV hashes are < 2^61; this pads a ragged stack of samples
_HASH_PAD = np.iinfo(np.int64).max


@dataclasses.dataclass
class TableSketch:
    name: str
    key_indicator: Optional[WMHSketch]  # x^{1[K]} (host oracle; None if disabled)
    values: Optional[WMHSketch]         # x^{V}
    values_sq: Optional[WMHSketch]      # x^{V^2}
    sample: KMVSketch            # KMV keyed sample of (key -> value): the
                                 # correlation sketch of Santos et al. 2021
    n_rows: int
    value_shift: float = 0.0     # the centring shift of the value fields


@dataclasses.dataclass
class SearchResult:
    name: str
    join_size: float
    joinability: float           # join size / query rows
    sum_b: float
    mean_b: float
    corr: float


@jax.jit
def _corr_scores(join, sum_a, sum_b, sum_a2, sum_b2, prod, min_join):
    """Candidate scores: |sketch-estimated corr| among joinable rows,
    weighted by the evidence behind it, ``join / (join + min_join)``.

    All inputs are [Q, P] device arrays of inner-product estimates.  Rows
    failing ``join >= min_join`` score -1 so the host can drop them.  The
    five-estimate correlation is noisy where few sketch samples collide:
    over shared hot key domains (days, zip codes) many tables pass
    ``min_join`` on one or two collisions, and their estimated |corr| is
    often clipped to 1, above truly correlated tables with far larger
    joins.  The weight halves a correlation measured over a join of
    ``min_join`` rows and leaves one over a much larger join nearly
    whole.  A non-positive ``min_join`` sets no evidence scale: weight 1.
    The host re-ranks the survivors by their sample correlation.  One
    jitted executable serves both the single-device and the sharded
    ranking path, so scores are bitwise identical between them.
    """
    var_a = join * sum_a2 - sum_a * sum_a
    var_b = join * sum_b2 - sum_b * sum_b
    cov = join * prod - sum_a * sum_b
    ok = (var_a > 0) & (var_b > 0)
    corr = jnp.where(ok, cov * jax.lax.rsqrt(jnp.where(ok, var_a * var_b, 1.0)),
                     0.0)
    corr = jnp.clip(corr, -1.0, 1.0)
    keep = join >= min_join
    weight = jnp.where(min_join > 0, join / (join + min_join), 1.0)
    return jnp.where(keep, jnp.abs(corr) * weight, -1.0)


class DatasetSearchIndex:
    """Sketch once, query many times -- the data-lake discovery pattern."""

    def __init__(self, m: int = 256, seed: int = 0, key_space: int = 2 ** 31,
                 backend: str = "device", keep_host_oracle: bool = True,
                 mesh=None, family: str = "icws", packed: bool = False):
        if backend not in ("device", "host"):
            raise ValueError(f"unknown backend {backend!r}")
        if family not in FAMILY_NAMES:
            raise ValueError(
                f"unknown sketch family {family!r}; choose from {FAMILY_NAMES}")
        if family != "icws" and backend == "host":
            raise ValueError(
                "backend='host' is the WMH/ICWS oracle path; the other "
                "families (cs, jl, ts, ps) serve on the device path only")
        self.m = m
        self.seed = seed
        self.key_space = key_space
        self.backend = backend
        # the device serving family, sized to the storage budget an
        # m-sample WMH/ICWS sketch occupies (registry accounting), so
        # icws/cs/jl indexes built with one m are storage-matched and the
        # paper's comparison is fair by construction.  family="icws"
        # resolves to exactly m samples -- the original path, bit for bit.
        self.family = make_family(family, storage=wmh_storage(m), seed=seed)
        # host oracle sketches are required to serve backend="host" queries;
        # symmetrically, the device corpus is only built when the index
        # serves (or may serve) device queries.  Linear families can never
        # serve the (WMH) host path, so they never pay the per-table host
        # sketching cost, whatever the flag says.
        self.keep_host_oracle = ((keep_host_oracle or backend == "host")
                                 and family == "icws")
        self.keep_device_corpus = backend == "device"
        self.mesh = mesh
        self.sketcher = WeightedMinHash(m=m, seed=seed)
        self.kmv = KMV(k=m, seed=seed)
        self.tables: List[TableSketch] = []
        # tenant id -> global table positions, ascending; device stores keep
        # the same assignment as row ranges (table i IS store row i), this
        # mirror serves the host path and the per-tenant TableSketch lookup
        self._tenant_tables: Dict[str, List[int]] = {}
        # the single device-resident copy of all three field corpora: the
        # store resolves the corpus axis, shards its buffers over it, and
        # keeps capacity divisible by the shard count
        # packed=True stores the corpus in the family's bit-packed wire
        # layout, which the estimate launches decode in-kernel; rankings
        # equal an unpacked index over bf16-roundtripped rows bit for bit
        # (see repro.data.store.CorpusStore)
        self.packed = bool(packed)
        self.store: Optional[CorpusStore] = (
            CorpusStore(family=self.family, fields=len(FIELDS), mesh=mesh,
                        packed=self.packed)
            if self.keep_device_corpus else None)
        self._corpus_axis = (self.store.corpus_axis
                             if self.store is not None else None)

    # -- ingestion ----------------------------------------------------------
    def vectorize(self, keys: np.ndarray, values: np.ndarray
                  ) -> Tuple[SparseVec, SparseVec, SparseVec]:
        keys = np.asarray(keys, dtype=np.int64)
        values = np.asarray(values, dtype=np.float64)
        # the sketch key domain is [0, key_space): fold raw int64 keys FIRST,
        # so two distinct keys that collide mod key_space aggregate the same
        # way in all three field vectors (pre-fix, the signed-value vector
        # deduplicated raw keys and then hit from_pairs' duplicate-index
        # error when folded keys collided, while the indicator aggregated)
        keys = keys % np.int64(self.key_space)
        # zero values would vanish from the sparse vector; nudge them so the
        # key stays represented (the paper's vectors assume non-zero values)
        safe = np.where(values == 0.0, 1e-9, values)
        # aggregate repeated (post-modulus) join keys: multiplicity for the
        # indicator, summed (squared) values for the value vectors
        ind = SparseVec.from_pairs(keys, np.ones_like(safe), self.key_space,
                                   sum_duplicates=True)
        sq = SparseVec.from_pairs(keys, safe ** 2, self.key_space,
                                  sum_duplicates=True)
        # signed value sums can cancel to exactly zero, which from_pairs
        # would drop; nudge post-aggregation so the key stays represented
        uniq, inverse = np.unique(keys, return_inverse=True)
        vsum = np.zeros(uniq.size, np.float64)
        np.add.at(vsum, inverse, safe)
        val = SparseVec.from_pairs(uniq, np.where(vsum == 0.0, 1e-9, vsum),
                                   self.key_space)
        return ind, val, sq

    def served_vectors(self, keys: np.ndarray, values: np.ndarray):
        """The field vectors the sketches hold: :meth:`vectorize` of the
        values minus a centring shift, plus the raw aggregated values (the
        KMV sample's input) and the shift.  The shift is the mean rounded
        to a multiple of the largest power of two at or below the standard
        deviation: within half a deviation of the mean, and an integer
        whenever the deviation is at least 1, so integer data stays exact.
        Returns ``((ind, val, sq), raw, shift)``."""
        values = np.asarray(values, dtype=np.float64)
        shift = 0.0
        if values.size:
            std = float(values.std())
            step = 2.0 ** np.floor(np.log2(std)) if std > 0 else 1.0
            shift = float(np.round(values.mean() / step) * step)
        ind, val, sq = self.vectorize(keys, values - shift)
        raw = val.values + shift * ind.values     # same sorted keys
        raw = SparseVec(indices=val.indices, n=val.n,
                        values=np.where(raw == 0.0, 1e-9, raw))
        return (ind, val, sq), raw, shift

    def add_table(self, name: str, keys: np.ndarray, values: np.ndarray,
                  tenant: Optional[str] = None):
        """Sketch one table into the corpus; ``tenant`` scopes it to a
        logical corpus inside the shared arena (see :meth:`query`)."""
        (ind, val, sq), raw, shift = self.served_vectors(keys, values)
        if self.store is not None:
            # device path: one [3, N] kernel launch sketches all three
            # fields; the rows append in place into the canonical store
            with _obs.family_context(self.family.name):
                comps = self.family.sketch_rows([ind, val, sq])
                self.store.append(*(c[:, None] for c in comps), tenant=tenant)
        self._register_table(name, keys, (ind, val, sq), raw, shift,
                             tenant=tenant)

    def add_tables_sharded(self, tables: Sequence[Tuple[str, np.ndarray,
                                                        np.ndarray]],
                           *, shards: int, tenant: Optional[str] = None):
        """Ingest many tables via a ``shards``-way parallel lake build.

        Every table's three field vectors are key-partitioned across the
        shards, each shard is sketched independently (the distributable
        part of a parallel build), and the shard corpora compact through
        the pairwise merge tree of :func:`repro.data.merge.build_sharded`
        before appending into this index's arena.  Per-table host-side
        metadata (the KMV correlation sample and, when kept, the host
        oracle sketches) is built single-stream -- the oracle path does
        not shard.

        Rankings off a sharded build match the single-stream build:
        bitwise for the linear families, exactly for the sampling families
        (modulo f32 tau rounding), and to within re-leveling noise for
        ICWS (top-k sets preserved on separated lakes).
        """
        if self.store is None:
            raise ValueError("sharded builds target the device corpus "
                             "(index constructed with backend='host')")
        tables = list(tables)
        if not tables:
            return
        metas = [(name, keys) + self.served_vectors(keys, values)
                 for name, keys, values in tables]
        with _obs.family_context(self.family.name):
            merged = build_sharded([m[2] for m in metas], family=self.family,
                                   shards=shards)
            self.store.append(*merged.field_arrays(), tenant=tenant)
        for meta in metas:
            self._register_table(*meta, tenant=tenant)

    def _register_table(self, name, keys, fields, raw, shift,
                        tenant: Optional[str] = None):
        host = {}
        if self.keep_host_oracle:
            host = {f: self.sketcher.sketch(v) for f, v in zip(FIELDS, fields)}
        if tenant is not None:
            self._tenant_tables.setdefault(str(tenant), []).append(
                len(self.tables))
        self.tables.append(TableSketch(
            name=name,
            key_indicator=host.get("key_indicator"),
            values=host.get("values"),
            values_sq=host.get("values_sq"),
            sample=self.kmv.sketch(raw),
            n_rows=len(keys), value_shift=shift))

    # -- tenancy -------------------------------------------------------------
    def tenants(self) -> Tuple[str, ...]:
        return tuple(self._tenant_tables)

    def _tenant_table_list(self, tenant: Optional[str]) -> List[TableSketch]:
        if tenant is None:
            return self.tables
        try:
            sel = self._tenant_tables[str(tenant)]
        except KeyError:
            raise KeyError(f"unknown tenant {tenant!r}; "
                           f"have {list(self._tenant_tables)}") from None
        return [self.tables[i] for i in sel]

    # -- queries ------------------------------------------------------------
    def query(self, keys: np.ndarray, values: np.ndarray,
              top_k: int = 10, min_join: float = 1.0,
              backend: Optional[str] = None,
              tenant: Optional[str] = None) -> List[SearchResult]:
        """Rank corpus tables by |corr| among sufficiently-joinable tables.

        ``tenant`` restricts the search to one logical corpus of the shared
        arena: only that tenant's tables are ranked, and -- because per-row
        estimates are independent of the surrounding arena rows -- the
        results are bitwise what a dedicated single-tenant index over the
        same tables would return.
        """
        if not self.tables:
            return []
        backend = backend or self.backend
        if backend == "host":
            return self._query_host(keys, values, top_k, min_join,
                                    tenant=tenant)
        # the fused batch engine with Q=1: same kernels, same numerics --
        # single and batched queries are one code path by construction
        with _obs.family_context(self.family.name):
            return self._query_batch_device(
                [(np.asarray(keys), np.asarray(values))], top_k, min_join,
                tenant=tenant)[0]

    def _assemble_results(self, scores, idx, join_c, sum_b_c, q_sample,
                          n_q: int, top_k: int,
                          tables: Optional[List[TableSketch]] = None
                          ) -> List[SearchResult]:
        """Host epilogue shared by all device paths: drop min_join failures,
        refine corr from the matched KMV samples, keep the ``top_k``
        candidates by refined |corr|.  ``join_c`` and ``sum_b_c`` are the
        candidates' estimates, aligned with ``idx``.  ``tables`` is the
        list ``idx`` indexes into -- the full corpus by default, a tenant's
        subset under tenant-scoped queries."""
        if tables is None:
            tables = self.tables
        keep = np.asarray(scores) >= 0                   # min_join passed
        cand = [tables[int(i)] for i in np.asarray(idx)[keep]]
        return self._rank(cand, self._sample_corrs(q_sample, cand),
                          join_c[keep], sum_b_c[keep], n_q, top_k)

    @staticmethod
    def _rank(cand: List[TableSketch], corr, join, sum_b_c, n_q: int,
              top_k: int) -> List[SearchResult]:
        """Results for ``cand``, best ``top_k`` by |corr| (stable).  ``join``
        and ``sum_b_c`` are the estimates ``<1[K_A], 1[K_B]>`` and ``<1[K_A],
        V_B - c_B>``; the sum adds the shift ``c_B`` back."""
        results = []
        for i in np.argsort(-np.abs(corr), kind="stable")[:top_k]:
            t = cand[i]
            js = max(float(join[i]), 0.0)
            sum_b = float(sum_b_c[i]) + t.value_shift * float(join[i])
            results.append(SearchResult(
                name=t.name, join_size=js, joinability=js / n_q,
                sum_b=sum_b, mean_b=sum_b / js if js > 0 else 0.0,
                corr=float(corr[i])))
        return results

    # -- batched queries -----------------------------------------------------
    def query_batch(self, queries: Sequence[Tuple[np.ndarray, np.ndarray]],
                    top_k: int = 10, min_join: float = 1.0,
                    backend: Optional[str] = None,
                    tenant: Optional[str] = None) -> List[List[SearchResult]]:
        """Answer Q ``(keys, values)`` queries in one shot.

        Device backend: ONE ``[3Q, N]`` ICWS sketch launch covers every field
        vector of every query, and ONE fused multi-field many-vs-many launch
        (per mesh shard when the corpus is sharded) computes all ``6 * Q * P``
        inner-product estimates.  Per-query results are identical to
        ``[self.query(k, v) for k, v in queries]``.

        Host backend: the host oracle has no kernel launches to amortize, so
        it simply loops the sequential oracle path.
        """
        queries = list(queries)
        if not self.tables or not queries:
            return [[] for _ in queries]
        backend = backend or self.backend
        if backend == "host":
            return [self._query_host(np.asarray(k), np.asarray(v),
                                     top_k, min_join, tenant=tenant)
                    for k, v in queries]
        with _obs.family_context(self.family.name):
            return self._query_batch_device(queries, top_k, min_join,
                                            tenant=tenant)

    def _estimate(self, qcomps, cbufs):
        """The fused single-device fields launch (unpacked or packed store
        buffers alike: a packed value plane decodes in-kernel)."""
        return self.family.estimate_fields(qcomps, cbufs,
                                           qmap=QFIELD, cmap=CFIELD)

    def _estimate_sharded(self, qcomps, cbufs):
        return ops.estimate_fields_sharded(
            self.family.estimate_fields, qcomps, cbufs, qmap=QFIELD,
            cmap=CFIELD, mesh=self.mesh, axis=self._corpus_axis)

    def _query_batch_device(self, queries, top_k: int, min_join: float,
                            tenant: Optional[str] = None
                            ) -> List[List[SearchResult]]:
        """The spans ``query.prep``, ``query.dispatch``, ``query.wait``,
        ``query.fetch`` and ``query.rerank`` cover the call in the order it
        runs; they wrap the statements that already block and add no
        sync, so the device sees the same stream with obs on or off.
        ``query.fetch`` copies the candidates' scores, indices, join and
        sum estimates in one transfer; its ``bytes``, ``Q * k * 16``, are
        counted in ``query.fetch_bytes_total``."""
        if self.store is None:
            raise ValueError("device corpus was not built at ingest "
                             "(index constructed with backend='host')")
        field_vecs: List[SparseVec] = []
        samples: List[KMVSketch] = []
        with _obs.span("query.prep"):
            for keys, values in queries:
                fields, raw, _ = self.served_vectors(keys, values)
                field_vecs.extend(fields)
                samples.append(self.kmv.sketch(raw))
        lanes = self._sketch_lanes(field_vecs) if _obs.enabled() else {}
        with _obs.span("query.dispatch", **lanes):
            *cands, tables = self._rank_on_device(
                field_vecs, len(queries), top_k, min_join, tenant)
        with _obs.span("query.wait"):
            jax.block_until_ready(cands)
        with _obs.span("query.fetch") as sp:
            scores, idx, join_c, sum_b_c = fetched = jax.device_get(cands)
            if _obs.enabled():
                nbytes = sum(a.nbytes for a in fetched)
                sp.set("bytes", nbytes)
                _obs.counter("query.fetch_bytes_total",
                             family=self.family.name).inc(nbytes)
        with _obs.span("query.rerank"):
            return [
                self._assemble_results(scores[qi], idx[qi], join_c[qi],
                                       sum_b_c[qi], samples[qi],
                                       n_q=max(len(queries[qi][0]), 1),
                                       top_k=top_k, tables=tables)
                for qi in range(len(queries))]

    def _sketch_lanes(self, field_vecs: List[SparseVec]) -> dict:
        """The query sketch batch's ``rows`` (3Q), padded non-zero
        ``width`` (:func:`padded_width`, as the batch padding computes it)
        and real non-zeros ``nnz``, for the ``query.dispatch`` span,
        counted in ``query.sketch_lanes_total``.  Host-known: no sync."""
        nnz = np.fromiter((v.nnz for v in field_vecs), np.int64,
                          count=len(field_vecs))
        rows, width, real = len(field_vecs), padded_width(nnz), int(nnz.sum())
        for kind, n in (("real", real), ("pad", rows * width - real)):
            _obs.counter("query.sketch_lanes_total", family=self.family.name,
                         kind=kind).inc(n)
        return {"rows": rows, "width": width, "nnz": real}

    def _rank_on_device(self, field_vecs: List[SparseVec], Q: int,
                        top_k: int, min_join: float,
                        tenant: Optional[str]):
        """Enqueue the query sketch, the fields launch, scoring, top-k and
        the candidates' gather; returns the candidates' scores, indices,
        join and sum_b estimates (each ``[Q, k]``, on the device) and the
        tables the indices refer to."""
        # one kernel launch sketches all 3Q query field vectors; each
        # component reshapes [3Q, ...] -> [3, Q, ...] for the fields launch
        qcomps = tuple(
            jnp.swapaxes(c.reshape((Q, 3) + c.shape[1:]), 0, 1)
            for c in self.family.sketch_rows(field_vecs))

        # one fused launch (per corpus shard): all six field-pair estimates
        # for every query, straight off the canonical store buffers (unused
        # capacity rows are inert and sliced out of the estimates below)
        cbufs = self.store.buffers()
        tables = self.tables
        if tenant is not None:
            # tenant-scoped query against the shared arena.  Per-row
            # estimates are independent of the surrounding rows, so both
            # routes below are bitwise what a dedicated single-tenant store
            # would produce.
            ranges = self.store.tenant_ranges(tenant)
            tables = self._tenant_table_list(tenant)
            P = len(tables)
            if len(ranges) == 1:
                # contiguous tenant: slice the arena buffers before the
                # launch -- per-query cost scales with THIS tenant's rows,
                # not the arena (the performance-isolation fast path)
                lo, hi = ranges[0]
                est = self._estimate(qcomps,
                                     tuple(c[:, lo:hi] for c in cbufs))
            else:
                # fragmented tenant: full-arena launch, gather the tenant's
                # estimate columns (O(arena) compute, exact results)
                if self._corpus_axis is not None:
                    est = self._estimate_sharded(qcomps, cbufs)
                else:
                    est = self._estimate(qcomps, cbufs)
                est = est[:, :, jnp.asarray(self.store.tenant_rows(tenant))]
            est = est[:, :, :P]
            k = min(max(top_k, CANDIDATES), P)
            score = _corr_scores(est[0], est[1], est[2], est[3], est[4],
                                 est[5], jnp.float32(min_join))
            scores, idx = ops.top_k(score, k)
        else:
            if self._corpus_axis is not None:
                est = self._estimate_sharded(qcomps, cbufs)    # [6, Q, cap]
            else:
                est = self._estimate(qcomps, cbufs)
            P = len(self.tables)
            est = est[:, :, :P]

            k = min(max(top_k, CANDIDATES), P)
            score = _corr_scores(est[0], est[1], est[2], est[3], est[4],
                                 est[5], jnp.float32(min_join))
            if self._corpus_axis is not None:
                scores, idx = ops.sharded_top_k(score, k, mesh=self.mesh,
                                                axis=self._corpus_axis)
            else:
                scores, idx = ops.top_k(score, k)
        join_c, sum_b_c = ops.take_candidates(est, idx,
                                              planes=CANDIDATE_PLANES)
        return scores, idx, join_c, sum_b_c, tables

    # -- host oracle (the original numpy implementation, cross-checked) -----
    def _stack(self, field: str) -> StackedWMH:
        return stack_wmh([getattr(t, field) for t in self.tables])

    def _query_host(self, keys, values, top_k: int, min_join: float,
                    tenant: Optional[str] = None) -> List[SearchResult]:
        # guard per-query backend overrides too: a non-ICWS index must
        # never silently answer from the WMH oracle instead of its own
        # sketch method (the constructor enforces the same rule up front)
        if self.family.name != "icws":
            raise ValueError(
                "backend='host' is the WMH/ICWS oracle path; this index "
                f"serves the {self.family.name!r} family on the device path "
                "only")
        if not self.keep_host_oracle or self.tables[0].key_indicator is None:
            raise ValueError("host oracle sketches were not kept at ingest "
                             "(keep_host_oracle=False)")
        (ind, _, _), raw, _ = self.served_vectors(keys, values)
        q_ind = self.sketcher.sketch(ind)
        tables = self._tenant_table_list(tenant)
        P = len(tables)

        def est(q: WMHSketch, field: str) -> np.ndarray:
            A = stack_wmh([q] * P)
            return self.sketcher.estimate_batch(
                A, stack_wmh([getattr(t, field) for t in tables]))

        join = est(q_ind, "key_indicator")                  # <1A, 1B>
        sum_b = est(q_ind, "values")                        # <1A, VB - cB>
        keep = np.flatnonzero(np.maximum(join, 0.0) >= min_join)
        cand = [tables[i] for i in keep]
        corr = self._sample_corrs(self.kmv.sketch(raw), cand)
        return self._rank(cand, corr, join[keep], sum_b[keep],
                          max(len(keys), 1), top_k)

    def _sample_corrs(self, sa: KMVSketch, cand: Sequence[TableSketch],
                      min_pairs: int = 8) -> np.ndarray:
        """Sample Pearson correlation over the join of the query with each
        candidate, from matched KMV samples (Santos et al. 2021 correlation
        sketches); 0 where fewer than ``min_pairs`` samples match or either
        side is constant.

        Matched hashes within the k smallest of the union form a uniform
        sample of joined rows; the *sample* correlation sidesteps the
        catastrophic moment cancellation that estimated E[x^2]-E[x]^2
        suffers under sketch noise.  The device path uses the (noisier)
        five-inner-product corr only to *select* candidates on device; this
        refines the survivors, all candidates in one vectorized pass over a
        ``[C, k]`` stack of their samples.
        """
        C = len(cand)
        ha = sa.hashes
        if C == 0 or ha.size == 0:
            return np.zeros(C)
        lens = np.array([t.sample.hashes.size for t in cand])
        width = max(int(lens.max()), 1)
        live = np.arange(width) < lens[:, None]
        hb = np.full((C, width), _HASH_PAD, np.int64)
        vb = np.zeros((C, width))
        hb[live] = np.concatenate([t.sample.hashes for t in cand])
        vb[live] = np.concatenate([t.sample.values for t in cand])
        # tau: the kk-th smallest distinct hash of each union
        both = np.sort(np.concatenate(
            [np.broadcast_to(ha, (C, ha.size)), hb], axis=1), axis=1)
        new = np.ones(both.shape, bool)
        new[:, 1:] = both[:, 1:] != both[:, :-1]
        distinct = np.cumsum(new & (both != _HASH_PAD), axis=1)
        kk = np.minimum(self.kmv.k, distinct[:, -1])
        tau = both[np.arange(C), np.argmax(distinct >= kk[:, None], axis=1)]
        # matched hashes at or below tau, with their query values
        pos = np.minimum(np.searchsorted(ha, hb), ha.size - 1)
        match = live & (ha[pos] == hb) & (hb <= tau[:, None])
        va = sa.values[pos]
        n = match.sum(axis=1)
        nz = np.maximum(n, 1)
        da = np.where(match, va - (va * match).sum(1, keepdims=True)
                      / nz[:, None], 0.0)
        db = np.where(match, vb - (vb * match).sum(1, keepdims=True)
                      / nz[:, None], 0.0)
        ssa, ssb = (da * da).sum(1), (db * db).sum(1)
        ok = (n >= min_pairs) & (ssa > 0) & (ssb > 0)
        corr = (da * db).sum(1) / np.sqrt(np.where(ok, ssa * ssb, 1.0))
        return np.where(ok, np.clip(corr, -1.0, 1.0), 0.0)

    def storage_doubles(self) -> float:
        """Serving-sketch storage (three fields per table, paper accounting)."""
        if self.store is not None:
            return self.store.storage_doubles()
        # host-only index: same accounting, counted from the oracle sketches
        return (len(self.tables) * len(FIELDS)
                * self.family.storage_doubles_per_row())
