"""The ``SketchFamily`` contract: device-resident serving for any sketch.

The paper's headline result is a head-to-head -- weighted MinWise hashing
vs the linear sketches (CountSketch, JL) -- and this module is what lets
the *serving stack* run that comparison live instead of only in host-numpy
benchmarks.  A family bundles everything the corpus store and the query
engine need to know about one sketch method:

  * **Buffer layout** (``components``): the per-row device buffers a
    :class:`repro.data.store.CorpusStore` preallocates.  ICWS rows are
    ``(fp [m] int32, val [m] f32, norm [] f32)``; linear rows are a single
    dense ``[R, W]`` f32 table (JL is the R = 1, W = m case).
  * **Inert-spare-row rule** (``ComponentSpec.fill``): the fill value that
    makes unused capacity rows estimate to exactly zero, so query launches
    run on full-capacity buffers and stay bitwise identical to exact-size
    arrays.  ICWS fingerprints fill with the corpus pad sentinel and norms
    with zero; linear tables fill with zero (a zero table dots to zero) --
    no sentinel machinery at all.
  * **Sketch launch** (``sketch_rows``): one padded-batch Pallas launch
    turning B sparse vectors into B buffer rows.
  * **Fused estimate launch** (``estimate_fields`` and its mesh-sharded
    twin): all (query-field, corpus-field) pairs of a Q-query batch in ONE
    kernel launch -- the ICWS collision kernel, or MXU matmuls with a
    median-of-reps epilogue for the linear families.
  * **Storage accounting** (``storage_doubles_per_row``) and storage-matched
    construction (:func:`make_family`), using the same per-method sizing as
    :mod:`repro.core.registry` so cross-family comparisons are
    storage-fair by construction.
  * **Host oracle** (``host_oracle``): the numpy sketcher sharing the
    kernel RNG contract (:class:`repro.core.ICWS`,
    :class:`repro.core.linear.CountSketchU32`,
    :class:`repro.core.linear.JLU32`) that device estimates are
    cross-checked against.

``DatasetSearchIndex(family="cs")`` / ``SketchSearchService(family="jl")``
thread one of these through the whole stack; ``family="icws"`` reproduces
the original ICWS path bit for bit.  The sampling families (``"ts"`` /
``"ps"``, arXiv:2309.16157) add a third estimator geometry: fixed-slot
coordinate samples matched by *key equality* rather than slot position,
served by the key-match contraction kernel in
:mod:`repro.kernels.sample_estimate`.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import registry, u32
from repro.core.dmh import DMH
from repro.core.icws import ICWS
from repro.core.linear import REPS, CountSketchU32, JLU32
from repro.core.sampling import (SAMPLE_HASH_STREAM, PrioritySamplingU32,
                                 ThresholdSamplingU32)
from repro.core.types import SparseVec
from repro.kernels import ops
from repro.kernels.common import (DMH_BETA_STREAM, DMH_BIN_STREAM,
                                  DMH_C1_STREAM, DMH_C2_STREAM,
                                  DMH_DENSIFY_STREAM, DMH_FP_STREAM,
                                  DMH_R1_STREAM, DMH_R2_STREAM,
                                  ICWS_BETA_STREAM, ICWS_C1_STREAM,
                                  ICWS_C2_STREAM, ICWS_FP_STREAM,
                                  ICWS_R1_STREAM, ICWS_R2_STREAM,
                                  densify_probes, hash_u32, salt_for,
                                  uniform01)
from repro.kernels.estimate import CORPUS_PAD_FP
from repro.kernels.packed import pack_halfwords_f32, unpack_halfwords_f32
from repro.kernels.ref import BIG

from .ingest import (dmh_sketch_batch, pad_linear_batch, pad_sample_batch,
                     sketch_batch)


@dataclasses.dataclass(frozen=True)
class ComponentSpec:
    """One per-row buffer of a family's corpus layout.

    A store allocates each component as ``[fields, capacity, *trailing]``
    with every element set to ``fill`` -- the value that keeps unallocated
    rows inert under the family's estimate launch.
    """

    name: str
    trailing: Tuple[int, ...]
    dtype: jnp.dtype
    fill: float


@dataclasses.dataclass(frozen=True)
class ICWSFamily:
    """ICWS (weighted MinWise) serving family -- the paper's method.

    Rows are (fingerprints, sampled values, norm); estimation is the fused
    collision kernel.  This family IS the pre-refactor serving path: it
    calls the same jitted launches with the same arguments, so rankings
    are bitwise unchanged.
    """

    m: int
    seed: int = 0
    name: str = dataclasses.field(default="icws", init=False)

    @property
    def components(self) -> Tuple[ComponentSpec, ...]:
        # argkeys (the per-sample winning key) rides LAST so every consumer
        # of the first three components -- estimate launches, host
        # estimators, field maps -- is layout-compatible with pre-argkeys
        # code.  It is only read by the merge path; spare rows fill with 0,
        # which the estimate kernels never look at.
        return (ComponentSpec("fingerprints", (self.m,), jnp.int32,
                              CORPUS_PAD_FP),
                ComponentSpec("values", (self.m,), jnp.float32, 0.0),
                ComponentSpec("norms", (), jnp.float32, 0.0),
                ComponentSpec("argkeys", (self.m,), jnp.int32, 0.0))

    def storage_doubles_per_row(self) -> float:
        """Paper accounting: 1.5 doubles per sample + 1 norm.  The argkeys
        merge sidecar is deliberately NOT charged: the paper's storage
        x-axis prices the *estimation* state, and dropping argkeys (serving
        a frozen, unmergeable corpus) loses nothing at query time."""
        return 1.5 * self.m + 1.0

    def sketch_rows(self, vecs: Sequence[SparseVec], *, bucket: int = 256):
        """One ICWS kernel launch: B sparse vectors -> (fp, val, norm,
        argkey) rows."""
        return sketch_batch(vecs, m=self.m, seed=self.seed, bucket=bucket)

    def estimate_fields(self, q, c, *, qmap, cmap):
        fq, vq, nq = q[0], q[1], q[2]
        fpc, vc, nc = c[0], c[1], c[2]
        return ops.icws_estimate_fields(fq, vq, nq, fpc, vc, nc,
                                        qmap=qmap, cmap=cmap)

    def estimate_fields_sharded(self, q, c, *, qmap, cmap, mesh, axis):
        fq, vq, nq = q[0], q[1], q[2]
        fpc, vc, nc = c[0], c[1], c[2]
        return ops.icws_estimate_fields_sharded(fq, vq, nq, fpc, vc, nc,
                                                qmap=qmap, cmap=cmap,
                                                mesh=mesh, axis=axis)

    @property
    def packed_components(self) -> Tuple[ComponentSpec, ...]:
        """Packed wire format: fingerprints stay full i32 lanes (31-bit
        exact-match state), values are a bf16 plane (truncated, see
        :mod:`repro.kernels.packed`), and the argkeys merge sidecar is
        dropped -- packed corpora are frozen serving state, 6m + 4 bytes
        per row vs 12m + 4 unpacked (50%)."""
        return (ComponentSpec("fingerprints", (self.m,), jnp.int32,
                              CORPUS_PAD_FP),
                ComponentSpec("packed_values", (self.m,), jnp.bfloat16, 0.0),
                ComponentSpec("norms", (), jnp.float32, 0.0))

    def pack_rows(self, rows):
        """(fp, val, norm[, argkey]) -> packed components, any leading dims.
        Values are bf16-truncated (exact thereafter); argkeys are dropped."""
        return (jnp.asarray(rows[0]).astype(jnp.int32),
                pack_halfwords_f32(rows[1]),
                jnp.asarray(rows[2]).astype(jnp.float32))

    def unpack_rows(self, rows):
        """Packed components -> unpacked-layout rows, bitwise the fixpoint
        of ``pack_rows`` (pack(unpack(p)) == p).  The argkeys sidecar comes
        back zeroed: packed rows are frozen and cannot re-enter the merge
        path."""
        fp, w, norm = (jnp.asarray(x) for x in rows)
        return (fp.astype(jnp.int32), unpack_halfwords_f32(w),
                norm.astype(jnp.float32), jnp.zeros(fp.shape, jnp.int32))

    def merge_rows(self, a, b):
        """Coordinated per-slot min-merge of row-aligned ICWS components.

        ``a`` and ``b`` are same-shape component tuples ``(fp [..., m], val
        [..., m], norm [...], argkey [..., m])`` sketching *disjoint
        partitions* of the same underlying vectors.  Device twin of
        :meth:`repro.core.icws.ICWS.merge`: both shard winners are
        re-scored under the merged norm (variates redrawn from (sample,
        key) -- the shared u32 streams), the smaller ICWS hash wins, and
        its fingerprint is re-derived at the re-leveled weight.  Ties break
        toward the smaller key, so the merge commutes bitwise.
        """
        fpa, va, na, ka = (jnp.asarray(x) for x in a)
        fpb, vb, nb, kb = (jnp.asarray(x) for x in b)
        t = jnp.arange(self.m, dtype=jnp.int32)
        # exact identity when one side is empty: sqrt(n^2) may round, so
        # pass the live norm through untouched
        norm_q = jnp.sqrt(na * na + nb * nb)
        norm_c = jnp.where(na == 0, nb, jnp.where(nb == 0, na, norm_q))
        safe_c = jnp.maximum(norm_c, jnp.float32(1e-37))[..., None]

        def rescore(fp, val, norm, key):
            z = val * (norm[..., None] / safe_c)
            w = z * z
            kk = key.astype(jnp.uint32)

            def u(stream):
                return uniform01(kk, salt_for(self.seed, stream, t))

            r = -jnp.log(u(ICWS_R1_STREAM) * u(ICWS_R2_STREAM))
            c = -jnp.log(u(ICWS_C1_STREAM) * u(ICWS_C2_STREAM))
            beta = u(ICWS_BETA_STREAM)
            logw = jnp.log(jnp.maximum(w, jnp.float32(1e-37)))
            lvl = jnp.floor(logw / r + beta)
            y = jnp.exp(r * (lvl - beta))
            av = c / (y * jnp.exp(r))
            av = jnp.where((fp < 0) | (w <= 0), jnp.float32(BIG), av)
            return z, av, lvl.astype(jnp.int32)

        za, aa, la = rescore(fpa, va, na, ka)
        zb, ab, lb = rescore(fpb, vb, nb, kb)
        pick_b = (ab < aa) | ((ab == aa)
                             & (kb.astype(jnp.uint32) < ka.astype(jnp.uint32)))
        key_c = jnp.where(pick_b, kb, ka)
        lvl_c = jnp.where(pick_b, lb, la)
        val_c = jnp.where(pick_b, zb, za)
        fpbits = hash_u32(
            key_c.astype(jnp.uint32)
            ^ (lvl_c.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)),
            salt_for(self.seed, ICWS_FP_STREAM, t))
        fp_c = (fpbits & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
        dead = jnp.minimum(aa, ab) >= BIG
        return (jnp.where(dead, -1, fp_c),
                jnp.where(dead, 0.0, val_c).astype(jnp.float32),
                norm_c.astype(jnp.float32),
                jnp.where(dead, 0, key_c).astype(jnp.int32))

    def host_oracle(self) -> ICWS:
        return ICWS(m=self.m, seed=self.seed)


@dataclasses.dataclass(frozen=True)
class DMHFamily(ICWSFamily):
    """DMH (densified one-permutation weighted MinHash) serving family.

    Same wire layout, storage accounting, packed format, and fused
    estimate launches as :class:`ICWSFamily` -- rows are ``(fingerprints,
    values, norm, argkeys)`` consumed by the same collision kernels -- but
    the *build* is O(c * nnz + m) per vector instead of O(nnz * m),
    with ``c = dmh_replication(m) <= 4``: one binning pass over the
    non-zeros (pseudo-key-replicated for m > 64 to debias the restricted
    collision law -- see :func:`repro.core.dmh.dmh_replication`) with an
    in-kernel densification epilogue
    (:mod:`repro.kernels.dmh_sketch`).  Only the three members
    that touch sketch construction differ: the sketch launch, the
    union-merge (which must recover bin origins and re-densify), and the
    host oracle.
    """

    name: str = dataclasses.field(default="dmh", init=False)

    def sketch_rows(self, vecs: Sequence[SparseVec], *, bucket: int = 256):
        """One DMH kernel launch: B sparse vectors -> (fp, val, norm,
        argkey) rows."""
        return dmh_sketch_batch(vecs, m=self.m, seed=self.seed,
                                bucket=bucket)

    def merge_rows(self, a, b):
        """Coordinated union-merge of row-aligned DMH components.

        Device twin of :meth:`repro.core.dmh.DMH.merge`.  DMH rows store
        no occupancy bitmap, but origins are recoverable from the layout:
        bin t holds its own minimum (not a densified copy) iff
        ``bin(argkey[t]) == t``.  Origin winners re-score under the merged
        norm (DMH streams at t = bin), strict-< picks the winner with ties
        toward the smaller key (commutative), and bins with no origin on
        either side re-densify from the merged occupancy through the same
        probe sequence the sketch kernel uses.
        """
        fpa, va, na, ka = (jnp.asarray(x) for x in a)
        fpb, vb, nb, kb = (jnp.asarray(x) for x in b)
        t = jnp.arange(self.m, dtype=jnp.int32)
        norm_q = jnp.sqrt(na * na + nb * nb)
        norm_c = jnp.where(na == 0, nb, jnp.where(nb == 0, na, norm_q))
        safe_c = jnp.maximum(norm_c, jnp.float32(1e-37))[..., None]
        bin_salt = salt_for(self.seed, DMH_BIN_STREAM, jnp.uint32(0))

        def rescore(fp, val, norm, key):
            kk = key.astype(jnp.uint32)
            bins = (hash_u32(kk, bin_salt)
                    % jnp.uint32(self.m)).astype(jnp.int32)
            origin = (fp >= 0) & (bins == t)
            z = val * (norm[..., None] / safe_c)
            w = z * z

            def u(stream):
                return uniform01(kk, salt_for(self.seed, stream, t))

            r = -jnp.log(u(DMH_R1_STREAM) * u(DMH_R2_STREAM))
            c = -jnp.log(u(DMH_C1_STREAM) * u(DMH_C2_STREAM))
            beta = u(DMH_BETA_STREAM)
            logw = jnp.log(jnp.maximum(w, jnp.float32(1e-37)))
            lvl = jnp.floor(logw / r + beta)
            y = jnp.exp(r * (lvl - beta))
            av = c / (y * jnp.exp(r))
            av = jnp.where(origin & (w > 0), av, jnp.float32(BIG))
            return z, av, lvl.astype(jnp.int32)

        za, aa, la = rescore(fpa, va, na, ka)
        zb, ab, lb = rescore(fpb, vb, nb, kb)
        pick_b = (ab < aa) | ((ab == aa)
                             & (kb.astype(jnp.uint32) < ka.astype(jnp.uint32)))
        key_c = jnp.where(pick_b, kb, ka)
        lvl_c = jnp.where(pick_b, lb, la)
        val_c = jnp.where(pick_b, zb, za)
        fpbits = hash_u32(
            key_c.astype(jnp.uint32)
            ^ (lvl_c.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)),
            salt_for(self.seed, DMH_FP_STREAM, t))
        fp_c = (fpbits & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
        occ = jnp.minimum(aa, ab) < BIG
        fp_c = jnp.where(occ, fp_c, -1)
        val_c = jnp.where(occ, val_c, 0.0).astype(jnp.float32)
        key_c = jnp.where(occ, key_c, 0).astype(jnp.int32)
        # re-densify: same reseeded probes as the sketch kernel, applied
        # to the merged origin occupancy
        J = densify_probes(self.m)
        js = jnp.arange(J, dtype=jnp.int32)
        psalt = salt_for(self.seed, DMH_DENSIFY_STREAM, js)
        src = (hash_u32(t[:, None].astype(jnp.uint32), psalt[None, :])
               % jnp.uint32(self.m)).astype(jnp.int32)      # [m, J]
        occ_p = jnp.take(occ, src, axis=-1)                 # [..., m, J]
        has = jnp.any(occ_p, axis=-1)
        firstj = jnp.argmax(occ_p, axis=-1).astype(jnp.int32)
        src_w = (hash_u32(t.astype(jnp.uint32),
                          salt_for(self.seed, DMH_DENSIFY_STREAM, firstj))
                 % jnp.uint32(self.m)).astype(jnp.int32)
        fallback = jnp.argmax(occ, axis=-1).astype(jnp.int32)[..., None]
        src_sel = jnp.where(has, src_w, fallback)
        need = (~occ) & jnp.any(occ, axis=-1)[..., None]

        def borrow(x):
            return jnp.where(need,
                             jnp.take_along_axis(x, src_sel, axis=-1), x)

        return (borrow(fp_c), borrow(val_c), norm_c.astype(jnp.float32),
                borrow(key_c))

    def host_oracle(self) -> DMH:
        return DMH(m=self.m, seed=self.seed)


class _LinearFamily:
    """Shared serving plumbing of the linear families (S(a) = Pi a).

    Rows are one dense ``[R, W]`` f32 table; estimation is per-rep MXU
    dots + a median-of-reps epilogue (R = 1 for JL, where the median is
    the dot itself).  Everything is zero-fill inert: empty sketches, spare
    capacity, and padding all estimate to exactly zero.
    """

    reps: int
    width: int
    seed: int

    @property
    def components(self) -> Tuple[ComponentSpec, ...]:
        return (ComponentSpec("tables", (self.reps, self.width),
                              jnp.float32, 0.0),)

    def storage_doubles_per_row(self) -> float:
        """Paper accounting: every table cell is one double equivalent."""
        return float(self.reps * self.width)

    def _sketch_tables(self, keys, vals):
        raise NotImplementedError

    def sketch_rows(self, vecs: Sequence[SparseVec], *, bucket: int = 256):
        """One linear-sketch kernel launch: B sparse vectors -> [B, R, W]."""
        keys, vals = pad_linear_batch(vecs, bucket=bucket)
        return (self._sketch_tables(jnp.asarray(keys), jnp.asarray(vals)),)

    def estimate_fields(self, q, c, *, qmap, cmap):
        return ops.linear_estimate_fields(q[0], c[0], qmap=qmap, cmap=cmap)

    def estimate_fields_sharded(self, q, c, *, qmap, cmap, mesh, axis):
        return ops.linear_estimate_fields_sharded(q[0], c[0], qmap=qmap,
                                                  cmap=cmap, mesh=mesh,
                                                  axis=axis)

    @property
    def packed_components(self) -> Tuple[ComponentSpec, ...]:
        """Packed wire format: every table cell bf16-truncated -- half the
        unpacked ``[R, W]`` f32 bytes.  Zero-fill stays inert: the zero
        halfword decodes to a zero table."""
        return (ComponentSpec("packed_tables", (self.reps, self.width),
                              jnp.bfloat16, 0.0),)

    def pack_rows(self, rows):
        return (pack_halfwords_f32(rows[0]),)

    def unpack_rows(self, rows):
        return (unpack_halfwords_f32(rows[0]),)

    def merge_rows(self, a, b):
        """Exact merge by linearity: ``S(x + y) = S(x) + S(y)`` -- the
        row-aligned tables simply add.  Commutative and associative up to
        f32 addition order (bitwise exact on integer-valued data)."""
        return (jnp.asarray(a[0]) + jnp.asarray(b[0]),)


@dataclasses.dataclass(frozen=True)
class CSFamily(_LinearFamily):
    """CountSketch serving family (median of ``reps`` repetitions)."""

    width: int
    reps: int = REPS
    seed: int = 0
    name: str = dataclasses.field(default="cs", init=False)

    def _sketch_tables(self, keys, vals):
        return ops.countsketch_sparse(keys, vals, width=self.width,
                                      reps=self.reps, seed=self.seed)

    def host_oracle(self) -> CountSketchU32:
        return CountSketchU32(width=self.width, seed=self.seed,
                              reps=self.reps)


@dataclasses.dataclass(frozen=True)
class JLFamily(_LinearFamily):
    """JL / AMS projection serving family (a single ``[1, m]`` table row)."""

    m: int
    seed: int = 0
    name: str = dataclasses.field(default="jl", init=False)

    @property
    def reps(self) -> int:
        return 1

    @property
    def width(self) -> int:
        return self.m

    def _sketch_tables(self, keys, vals):
        return ops.jl_sketch(keys, vals, m=self.m, seed=self.seed)[:, None, :]

    def host_oracle(self) -> JLU32:
        return JLU32(m=self.m, seed=self.seed)


class _SamplingFamily:
    """Shared serving plumbing of the sampling families (TS/PS).

    Rows are fixed-slot coordinate samples ``(key [slots] i32, val [slots]
    f32, tau [] f32)`` -- see :mod:`repro.core.sampling` for the contract.
    Estimation is the unaligned key-match contraction
    (:mod:`repro.kernels.sample_estimate`): slots are matched by key
    equality, not position, and matches are reweighted by inverse inclusion
    probability.  Inert spare rows are corpus-pad-sentinel keys with zero
    values and zero tau (probability 0 on every slot), so they estimate to
    exactly zero with the same guard that excludes slot padding.

    Sketch *building* is host-side (:func:`repro.data.ingest.
    pad_sample_batch`): weighted sampling is per-vector select/top-k work,
    not a kernel-shaped reduction -- the device owns storage + estimation.
    """

    slots: int
    seed: int

    @property
    def components(self) -> Tuple[ComponentSpec, ...]:
        return (ComponentSpec("keys", (self.slots,), jnp.int32,
                              CORPUS_PAD_FP),
                ComponentSpec("values", (self.slots,), jnp.float32, 0.0),
                ComponentSpec("taus", (), jnp.float32, 0.0))

    def storage_doubles_per_row(self) -> float:
        """A key (i32) + value (f32) pair per slot is one 64-bit double
        equivalent, plus one double for the probability scale tau."""
        return float(self.slots) + 1.0

    def sketch_rows(self, vecs: Sequence[SparseVec], *, bucket: int = 256):
        """Host-build B sample rows (``bucket`` is a padded-batch knob of
        the kernel-ingest families; sampling rows are fixed-slot already)."""
        del bucket
        k, v, t = pad_sample_batch(vecs, slots=self.slots, method=self.name,
                                   seed=self.seed)
        return jnp.asarray(k), jnp.asarray(v), jnp.asarray(t)

    def estimate_fields(self, q, c, *, qmap, cmap):
        kq, vq, tq = q
        kc, vc, tc = c
        return ops.sample_estimate_fields(kq, vq, tq, kc, vc, tc,
                                          qmap=qmap, cmap=cmap)

    def estimate_fields_sharded(self, q, c, *, qmap, cmap, mesh, axis):
        kq, vq, tq = q
        kc, vc, tc = c
        return ops.sample_estimate_fields_sharded(kq, vq, tq, kc, vc, tc,
                                                  qmap=qmap, cmap=cmap,
                                                  mesh=mesh, axis=axis)

    @property
    def packed_components(self) -> Tuple[ComponentSpec, ...]:
        """Packed wire format: sample keys stay full i32 lanes (31-bit
        exact-match state -- the information floor of this layout), values
        are a bf16 plane, taus stay f32: 6S + 4 bytes per row vs 8S + 4
        unpacked (75%)."""
        return (ComponentSpec("keys", (self.slots,), jnp.int32,
                              CORPUS_PAD_FP),
                ComponentSpec("packed_values", (self.slots,), jnp.bfloat16,
                              0.0),
                ComponentSpec("taus", (), jnp.float32, 0.0))

    def pack_rows(self, rows):
        return (jnp.asarray(rows[0]).astype(jnp.int32),
                pack_halfwords_f32(rows[1]),
                jnp.asarray(rows[2]).astype(jnp.float32))

    def unpack_rows(self, rows):
        k, w, t = (jnp.asarray(x) for x in rows)
        return (k.astype(jnp.int32), unpack_halfwords_f32(w),
                t.astype(jnp.float32))

    def _merge_keep(self, live, h, vals, ta, tb):
        raise NotImplementedError

    def merge_rows(self, a, b):
        """Union re-subsampling of row-aligned sample components.

        ``a`` and ``b`` are ``(key [..., S], val [..., S], tau [...])``
        component tuples sampling *disjoint partitions* of the same
        vectors.  The kept slot sets are pooled, the merged scheme
        threshold is recomputed (TS: ``tau_c = tau_a + tau_b``; PS:
        ``T_c = min(T_a, T_b, T_cand)``), the coordinated hash re-decides
        every pooled slot, and survivors repack in the canonical
        ascending-key layout.  Runs host-side in float64, mirroring the
        builders in :mod:`repro.core.sampling` decision for decision --
        sampling is select/sort-shaped work, and bit-agreement with the
        host oracles matters more than device residency (the builders
        themselves are host-side for the same reason).
        """
        ka, va, ta = (np.asarray(x) for x in a)
        kb, vb, tb = (np.asarray(x) for x in b)
        S = self.slots
        keys = np.concatenate([ka, kb], axis=-1).astype(np.int64)
        vals = np.concatenate([va, vb], axis=-1).astype(np.float64)
        live = keys >= 0                       # slot pads are negative
        vals = np.where(live, vals, 0.0)
        lane = np.arange(2 * S, dtype=np.int64)
        big = np.int64(1) << 33                # above any 31-bit key
        srt = np.sort(np.where(live, keys, big + lane), axis=-1)
        if np.any((srt[..., 1:] == srt[..., :-1]) & (srt[..., 1:] < big)):
            raise ValueError("union-merge requires disjoint supports "
                             "(shared keys found in both rows)")
        salt = u32.salt_for(self.seed, SAMPLE_HASH_STREAM,
                            np.zeros(1, np.uint32))
        h = u32.uniform01(keys.astype(np.uint64).astype(np.uint32),
                          salt).astype(np.float64)
        keep, tau_c = self._merge_keep(live, h, vals,
                                       ta.astype(np.float64),
                                       tb.astype(np.float64))
        order = np.argsort(np.where(keep, keys, big + lane), axis=-1,
                           kind="stable")
        k_s = np.take_along_axis(keys, order, -1)[..., :S]
        v_s = np.take_along_axis(vals, order, -1)[..., :S]
        kept = np.take_along_axis(keep, order, -1)[..., :S]
        return (jnp.asarray(np.where(kept, k_s, -1).astype(np.int32)),
                jnp.asarray(np.where(kept, v_s, 0.0).astype(np.float32)),
                jnp.asarray(tau_c.astype(np.float32)))


@dataclasses.dataclass(frozen=True)
class TSFamily(_SamplingFamily):
    """Threshold Sampling serving family (expected-size-bounded sample)."""

    slots: int
    seed: int = 0
    name: str = dataclasses.field(default="ts", init=False)

    def _merge_keep(self, live, h, vals, ta, tb):
        # tau = ||v||^2 * slots / target: disjoint-support norms add, so
        # the merged tau is the sum and p_c = min(1, S v^2 / tau_c) only
        # shrinks -- re-flipping the same coordinated coin on the pooled
        # slots reproduces the build-once sample (see ThresholdSamplingU32
        # .merge for the overflow caveat).
        S = self.slots
        tau_c = ta + tb
        denom = np.where(tau_c > 0, tau_c, 1.0)[..., None]
        p = np.where(tau_c[..., None] > 0,
                     np.minimum(1.0, S * vals * vals / denom), 1.0)
        p = np.where(live, p, 0.0)
        keep = h < p
        over = keep.sum(axis=-1) > S
        if np.any(over):
            rank = np.where(keep, h / np.where(p > 0, p, 1.0), np.inf)
            pos = np.argsort(np.argsort(rank, axis=-1, kind="stable"),
                             axis=-1)
            keep = keep & (~over[..., None] | (pos < S))
        return keep, tau_c

    def host_oracle(self) -> ThresholdSamplingU32:
        return ThresholdSamplingU32(slots=self.slots, seed=self.seed)


@dataclasses.dataclass(frozen=True)
class PSFamily(_SamplingFamily):
    """Priority Sampling serving family (exactly-full fixed-size sample)."""

    slots: int
    seed: int = 0
    name: str = dataclasses.field(default="ps", init=False)

    def _merge_keep(self, live, h, vals, ta, tb):
        # T = slots / tau is each side's threshold rank (infinite when the
        # support fit); the union threshold is min(T_a, T_b, T_cand) with
        # T_cand the (S+1)-th smallest pooled rank.  Exactly build-once:
        # see PrioritySamplingU32.merge for the argument.
        S = self.slots
        t_a = np.where(ta > 0, S / np.where(ta > 0, ta, 1.0), np.inf)
        t_b = np.where(tb > 0, S / np.where(tb > 0, tb, 1.0), np.inf)
        sq = np.where(live, vals * vals, 1.0)
        rank = np.where(live, h / sq, np.inf)
        t_cand = np.sort(rank, axis=-1)[..., S]
        t_c = np.minimum(np.minimum(t_a, t_b), t_cand)
        keep = rank < t_c[..., None]
        tau_c = np.where(np.isinf(t_c), 0.0,
                         S / np.where(np.isinf(t_c), 1.0, t_c))
        return keep, tau_c

    def host_oracle(self) -> PrioritySamplingU32:
        return PrioritySamplingU32(slots=self.slots, seed=self.seed)


FAMILY_NAMES = ("icws", "cs", "jl", "ts", "ps", "dmh")


def make_family(name: str, *, storage: float, seed: int = 0):
    """Construct a serving family sized to a total storage budget.

    ``storage`` is the paper's x-axis -- total 64-bit-double equivalents
    per sketch -- and the per-method sizing is delegated to
    :mod:`repro.core.registry` (icws/dmh: ``m = (storage - 1) / 1.5``; cs:
    ``width = storage / reps``; jl: ``m = storage``; ts/ps:
    ``slots = storage - 1``), so families built from one budget are
    storage-matched and comparisons are fair.
    """
    if name == "icws":
        return ICWSFamily(m=registry.make_icws(storage).m, seed=seed)
    if name == "dmh":
        return DMHFamily(m=registry.make_dmh(storage).m, seed=seed)
    if name == "cs":
        host = registry.make_cs(storage)
        return CSFamily(width=host.width, reps=host.reps, seed=seed)
    if name == "jl":
        return JLFamily(m=registry.make_jl(storage).m, seed=seed)
    if name == "ts":
        return TSFamily(slots=registry.make_ts(storage).slots, seed=seed)
    if name == "ps":
        return PSFamily(slots=registry.make_ps(storage).slots, seed=seed)
    raise ValueError(
        f"unknown sketch family {name!r}; choose from {FAMILY_NAMES}")


def wmh_storage(m: int) -> float:
    """The storage budget an m-sample WMH/ICWS sketch occupies -- the
    anchor :class:`repro.data.dataset_search.DatasetSearchIndex` uses to
    size every family from its ``m`` parameter.  Delegates to the family's
    own accounting so the formula lives in exactly one place."""
    return ICWSFamily(m=m).storage_doubles_per_row()
