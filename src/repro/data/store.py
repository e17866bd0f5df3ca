"""Canonical field-stacked sketch store with amortized device-side append.

This is the single device-resident copy of a sketch corpus, for ANY serving
family (:mod:`repro.data.families`).  All F field corpora of a
dataset-search index (F = 3 for the §1.3 fields) live in one set of
preallocated per-component buffers ``[F, capacity, *trailing]``:

    icws      fingerprints [F, cap, m] i32 + values [F, cap, m] f32
              + norms [F, cap] f32
    cs / jl   tables [F, cap, R, W] f32          (JL: R = 1, W = m)

``append`` writes new rows into the buffers with
``jax.lax.dynamic_update_slice`` under a jit whose buffer arguments are
*donated*, so on accelerators the write is in place and costs O(rows
appended), not O(corpus).  When the corpus outgrows its capacity the buffers
double (classic amortized growth: total copy work over any append sequence
is O(final size)).  This replaces the old chunk-list scheme whose first
query after an append re-concatenated every row ever ingested.

Unused capacity rows are *inert* under the family's estimate launch: each
component fills with its family's ``ComponentSpec.fill`` -- the ICWS corpus
pad sentinel (``-2``, which never equals a query fingerprint) with zero
norms, or plain zeros for linear tables (a zero table dots to zero).  Query
paths therefore run directly on the full-capacity buffers -- no exact-size
slice of the corpus is ever materialized on the hot path -- and slice the
*estimates* (cheap, ``O(capacity)`` per query row) down to the live row
count.  Per-row estimates are bitwise independent of trailing capacity, so
results are identical to running on exact-size arrays.

On CPU (no buffer donation in XLA's CPU client) the update falls back to a
buffer copy; the scheme still never restacks chunk lists and becomes truly
in-place on TPU.

**Multi-tenant arena.**  One store can hold many logical corpora: every
``append(..., tenant=...)`` records the written row range in a per-tenant
row-range table, so N tenants share one set of device buffers (one
allocation, one growth schedule, one jit shape family) while queries
address a single tenant's rows.  Because per-row estimates are bitwise
independent of the surrounding rows, a tenant's results off the shared
arena equal a dedicated single-tenant store bit for bit -- the serving
stack exploits this by slicing (contiguous tenants) or gathering
(fragmented tenants) at query time.  Rows appended without a tenant belong
to the arena at large and are only visible to tenant-less queries.
"""
from __future__ import annotations

import contextlib
import functools
import warnings
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro import obs as _obs
from repro.distributed.sharding import corpus_axis
from repro.kernels.estimate import CORPUS_PAD_FP

from .families import ICWSFamily


@contextlib.contextmanager
def _quiet_cpu_donation():
    # XLA's CPU client has no buffer donation; jax warns once per shape at
    # compile time.  The copy fallback is this module's documented CPU
    # behavior, so the warning is noise here (donation works on TPU).
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield

# Corpus pad sentinel: the estimate kernels' own corpus padding fill
# (single definition in repro.kernels.estimate), so unused ICWS capacity
# rows never collide with any query fingerprint (queries pad with -1; live
# fingerprints are >= 0).  Linear families need no sentinel: their fill is
# plain zero.
PAD_FP = CORPUS_PAD_FP


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_rows(bufs, rows, off):
    zero = jnp.int32(0)
    return tuple(
        jax.lax.dynamic_update_slice(b, r, (zero, off) + (zero,) * (b.ndim - 2))
        for b, r in zip(bufs, rows))


@functools.partial(jax.jit, static_argnames=("cap", "fills"),
                   donate_argnums=(0,))
def _grow_buffers(bufs, *, cap: int, fills):
    return tuple(
        jnp.concatenate(
            [b, jnp.full((b.shape[0], cap - b.shape[1]) + b.shape[2:],
                         fill, b.dtype)], axis=1)
        for b, fill in zip(bufs, fills))


class CorpusStore:
    """Growable field-stacked device store of sketch rows.

    The buffer layout, inert-row fills, and storage accounting come from a
    :mod:`repro.data.families` ``SketchFamily``; the default (``family=None``
    with ``m`` given) is the ICWS family, preserving the original
    ``(fingerprints, values, norms)`` three-buffer contract bit for bit.

    ``fields=1`` is the generic single-corpus case, estimated with
    :func:`repro.kernels.ops.icws_estimate_fields` at ``qmap = cmap = (0,)``;
    ``fields=3`` backs :class:`repro.data.dataset_search.DatasetSearchIndex`
    with all three §1.3 field corpora in one canonical stack.

    ``packed=True`` switches the resident buffers to the family's bit-packed
    wire layout (``family.packed_components``): sketch values are stored as
    bf16 planes (truncated f32) and decoded *inside* the estimate kernels,
    cutting resident bytes/row to ~50% (icws / linear) or ~75%
    (ts / ps, whose 31-bit exact-match keys are the information floor).
    ``append`` still takes ordinary unpacked sketch rows -- they are
    validated against the family's unpacked contract, then packed via
    ``family.pack_rows`` before the device write, so ingest call sites are
    unchanged.  Query paths hand the packed buffers to the same
    ``family.estimate_fields`` launches, which decode the bf16 plane
    in-kernel; rankings are bitwise identical to an
    unpacked store holding the bf16-roundtripped rows.  Packed stores are
    frozen for merging: the ICWS packed layout drops the ``argkeys``
    re-leveling sidecar, so :func:`repro.data.merge.merge_stores` refuses
    them.
    """

    def __init__(self, m: "int | None" = None, fields: int = 1,
                 min_capacity: int = 64, mesh=None, row_multiple: int = 0,
                 family=None, packed: bool = False):
        if family is None:
            if m is None:
                raise ValueError("provide a family or an ICWS sample count m")
            family = ICWSFamily(m=int(m))
        elif m is not None:
            raise ValueError(
                "m and family are mutually exclusive: the family defines its "
                "own sketch size")
        if fields < 1:
            raise ValueError("fields must be >= 1")
        if min_capacity < 1:
            raise ValueError("min_capacity must be >= 1")
        self.family = family
        self.packed = bool(packed)
        # append always validates against the unpacked row contract; the
        # resident layout is the packed one when packed=True
        self._row_specs = tuple(family.components)
        self._specs = (tuple(family.packed_components) if self.packed
                       else self._row_specs)
        self._fills = tuple(s.fill for s in self._specs)
        self.m = getattr(family, "m", None)
        self.fields = int(fields)
        # a mesh with a multi-device corpus axis (see
        # repro.distributed.sharding.corpus_axis) shards the buffers over
        # their row dim at allocation, so the corpus memory -- not just the
        # query compute -- spreads across devices and no per-query
        # redistribution ever happens
        self.mesh = mesh
        self.corpus_axis = corpus_axis(mesh) if mesh is not None else None
        if self.corpus_axis is not None:
            self._shardings = tuple(
                NamedSharding(mesh, PartitionSpec(
                    None, self.corpus_axis, *(None,) * len(s.trailing)))
                for s in self._specs)
        else:
            self._shardings = None
        # round the capacity floor up to a multiple of row_multiple (the
        # corpus-axis size unless overridden): doubling preserves
        # divisibility, so every capacity this store ever allocates splits
        # evenly over the shards and the query path never re-pads rows
        if row_multiple < 1:
            row_multiple = (mesh.shape[self.corpus_axis]
                            if self.corpus_axis is not None else 1)
        self.row_multiple = int(row_multiple)
        self.min_capacity = (-(-int(min_capacity) // self.row_multiple)
                             * self.row_multiple)
        self._bufs = None
        self._size = 0
        self._cap = 0
        # tenant id -> ordered [start, stop) row ranges (coalesced when
        # consecutive appends land back to back)
        self._tenant_ranges: Dict[str, List[Tuple[int, int]]] = {}

    def __len__(self) -> int:
        return self._size

    @property
    def size(self) -> int:
        """Live rows per field."""
        return self._size

    @property
    def capacity(self) -> int:
        """Allocated rows per field (size <= capacity < 2 * max(size, min))."""
        return self._cap

    # -- ingestion -----------------------------------------------------------
    def append(self, *rows, tenant: "str | None" = None) -> None:
        """Append sketch rows, one array per family component, each
        ``[F, b, *trailing]`` (the leading F axis may be omitted when
        ``fields == 1`` -- e.g. ICWS ``[b, m]`` / ``[b]``).

        All components are validated against each other up front -- a
        row-count mismatch raises here, at ingest, never at query time.

        ``tenant`` assigns the written rows to a logical corpus inside the
        shared arena (see the module docstring); ``None`` leaves them in
        the tenant-less pool.
        """
        if len(rows) != len(self._row_specs):
            raise ValueError(
                f"{self.family.name} rows have {len(self._row_specs)} "
                f"components ({', '.join(s.name for s in self._row_specs)}); "
                f"got {len(rows)}")
        rows = [jnp.asarray(r, s.dtype) for r, s in zip(rows, self._row_specs)]
        if self.fields == 1:
            rows = [r[None] if r.ndim == 1 + len(s.trailing) else r
                    for r, s in zip(rows, self._row_specs)]
        lead = self._row_specs[0]
        if (rows[0].ndim != 2 + len(lead.trailing)
                or rows[0].shape[0] != self.fields
                or rows[0].shape[2:] != lead.trailing):
            raise ValueError(
                f"{lead.name} rows must be [{self.fields}, b, "
                f"{', '.join(map(str, lead.trailing))}]; "
                f"got {tuple(rows[0].shape)}")
        b = int(rows[0].shape[1])
        for r, s in zip(rows[1:], self._row_specs[1:]):
            if r.shape != (self.fields, b) + s.trailing:
                raise ValueError(
                    f"{s.name} rows {tuple(r.shape)} do not match "
                    f"{lead.name} rows "
                    f"{(self.fields, b) + s.trailing}")
        if b == 0:
            return
        with _obs.span("store.append", family=self.family.name, rows=b,
                       tenant=tenant):
            if self.packed:
                rows = [jnp.asarray(r, s.dtype) for r, s in
                        zip(self.family.pack_rows(tuple(rows)), self._specs)]
            self._reserve(self._size + b)
            with _quiet_cpu_donation():
                self._bufs = _write_rows(self._bufs, tuple(rows),
                                         jnp.int32(self._size))
            self._place()
        if tenant is not None:
            ranges = self._tenant_ranges.setdefault(str(tenant), [])
            if ranges and ranges[-1][1] == self._size:
                ranges[-1] = (ranges[-1][0], self._size + b)
            else:
                ranges.append((self._size, self._size + b))
        self._size += b
        if _obs.enabled():
            fam = self.family.name
            _obs.counter("store.appends_total", family=fam).inc()
            _obs.gauge("store.rows", family=fam).set(self._size)
            _obs.gauge("store.resident_bytes", family=fam).set(
                self._cap * self.fields * self.bytes_per_row())

    # -- tenancy -------------------------------------------------------------
    def tenants(self) -> Tuple[str, ...]:
        """Tenant ids in first-append order."""
        return tuple(self._tenant_ranges)

    def tenant_ranges(self, tenant: str) -> Tuple[Tuple[int, int], ...]:
        """The tenant's ordered, coalesced ``[start, stop)`` row ranges.

        A tenant whose appends were never interleaved with other writes has
        exactly one range -- the query path then serves it by slicing the
        shared buffers instead of gathering.
        """
        try:
            return tuple(self._tenant_ranges[str(tenant)])
        except KeyError:
            raise KeyError(f"unknown tenant {tenant!r}; "
                           f"have {list(self._tenant_ranges)}") from None

    def tenant_rows(self, tenant: str) -> np.ndarray:
        """Global row indices of the tenant's rows, ascending."""
        return np.concatenate(
            [np.arange(a, b, dtype=np.int64)
             for a, b in self.tenant_ranges(tenant)] or
            [np.zeros(0, np.int64)])

    def tenant_size(self, tenant: str) -> int:
        return int(sum(b - a for a, b in self.tenant_ranges(tenant)))

    def describe_tenants(self) -> Dict[str, Dict[str, float]]:
        """Per-tenant accounting: rows, row ranges, and the tenant's share
        of the paper's storage-doubles ledger."""
        per_row = self.fields * self.family.storage_doubles_per_row()
        return {
            t: {"rows": float(self.tenant_size(t)),
                "ranges": float(len(self.tenant_ranges(t))),
                "storage_doubles": float(self.tenant_size(t) * per_row)}
            for t in self._tenant_ranges}

    def _reserve(self, n: int) -> None:
        if n <= self._cap:
            return
        cap = max(self._cap, self.min_capacity)
        while cap < n:
            cap *= 2
        if self._bufs is None:
            F = self.fields
            self._bufs = tuple(
                jnp.full((F, cap) + s.trailing, s.fill, s.dtype)
                for s in self._specs)
        else:
            with _obs.span("store.grow", family=self.family.name,
                           capacity=cap):
                with _quiet_cpu_donation():
                    self._bufs = _grow_buffers(self._bufs, cap=cap,
                                               fills=self._fills)
            if _obs.enabled():
                _obs.counter("store.grows_total",
                             family=self.family.name).inc()
        self._cap = cap
        self._place()

    def _place(self) -> None:
        """Pin the buffers to their row-sharded placement.

        ``device_put`` onto an array's existing sharding is a no-op, so
        this only moves data when an allocation / growth / update changed
        the placement; single-device stores skip it entirely."""
        if self._shardings is None:
            return
        self._bufs = tuple(jax.device_put(b, s)
                           for b, s in zip(self._bufs, self._shardings))

    # -- views ---------------------------------------------------------------
    def buffers(self) -> Tuple[jnp.ndarray, ...]:
        """The canonical full-capacity device buffers, one per family
        component: ICWS ``(fp [F, cap, m], val [F, cap, m], norm [F, cap])``,
        linear families ``(tables [F, cap, R, W],)``.

        This is what query paths consume: unused capacity rows are inert
        under the family's estimate launch (pad-sentinel fingerprints and
        zero norms, or all-zero tables), so estimates over the buffers
        match estimates over exact-size arrays row for row -- callers slice
        the *estimates* to ``[..., :len(store)]``, never the corpus.

        .. warning:: the next :meth:`append` DONATES these exact arrays
           back to XLA for the in-place update, which invalidates them on
           backends with donation (TPU/GPU: using a stale reference raises
           ``Array has been deleted``).  Re-fetch per query; never cache
           the returned arrays across appends.
        """
        if self._size == 0:
            raise ValueError("empty corpus")
        return self._bufs

    def arrays(self) -> Tuple[jnp.ndarray, ...]:
        """Exact-size ``[F, P, *trailing]`` component slices (the leading F
        axis is dropped when ``fields == 1``).

        A transient copy when ``size < capacity`` -- intended for host-side
        cross-checks and tests; hot query paths use :meth:`buffers`.
        """
        if self._size == 0:
            raise ValueError("empty corpus")
        out = tuple(b[:, :self._size] for b in self._bufs)
        if self.fields == 1:
            return tuple(o[0] for o in out)
        return out

    def field_arrays(self) -> Tuple[jnp.ndarray, ...]:
        """Exact-size component slices, ALWAYS ``[F, P, *trailing]``.

        Like :meth:`arrays` but without the ``fields == 1`` F-axis drop --
        the uniform layout the merge layer (:mod:`repro.data.merge`)
        consumes and the family ``merge_rows`` contracts are written
        against.
        """
        if self._size == 0:
            raise ValueError("empty corpus")
        return tuple(b[:, :self._size] for b in self._bufs)

    def bytes_per_row(self) -> int:
        """Resident device bytes per stored sketch row (one field), straight
        from the component specs that size the buffers: ``sum(itemsize *
        prod(trailing))``.  This is the quantity the packed layout shrinks
        (the ``perf/scale`` gate compares packed vs unpacked stores) --
        distinct from :meth:`storage_doubles`, the paper's idealized
        double-equivalents ledger."""
        return int(sum(
            np.dtype(s.dtype).itemsize
            * int(np.prod(s.trailing, dtype=np.int64))
            for s in self._specs))

    def storage_doubles(self) -> float:
        """Paper accounting, per family (icws: 1.5 doubles per sample + 1
        norm per sketch; linear: one double equivalent per table cell)."""
        return self._size * self.fields * self.family.storage_doubles_per_row()
