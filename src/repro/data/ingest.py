"""Batch ingest helpers: pad sparse vectors into kernel layouts, sketch them.

The sketch kernels consume ``[B, N]`` padded batches.  Two padding
conventions exist, one per sketch-family class:

  * :func:`pad_sparse_batch` -- the ICWS layout: *normalized* squared
    weights + signed values + per-vector norms (the kernel masks ``w == 0``
    lanes as padding).
  * :func:`pad_linear_batch` -- the linear (CS/JL) layout: raw signed
    values, zero-valued padding (a zero value contributes sign * 0 = 0 to a
    linear sketch, so padding is inert with no mask at all).

Both fill with one flat numpy scatter over the concatenated indices/values
of the whole batch -- no per-vector Python loop -- and round ``N`` up to a
``bucket`` multiple so repeated ingests reuse one jit cache entry.

The sampling families (TS/PS) ingest differently: :func:`pad_sample_batch`
*builds the sketch itself* on the host (weighted sampling is a per-vector
select/top-k, not a kernel-shaped reduction) and emits finished fixed-slot
sample rows ``(key [B, slots], val [B, slots], tau [B])`` that the
key-match estimate kernel consumes directly.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.dmh import dmh_replication, replicate_keys
from repro.core.sampling import priority_sample, threshold_sample
from repro.core.types import SparseVec
from repro.kernels import ops
from repro.kernels.sample_estimate import SAMPLE_QUERY_PAD_KEY


def _flat_scatter(vecs: Sequence[SparseVec], active: np.ndarray,
                  nnz: np.ndarray):
    """Row/col scatter coordinates + concatenated indices/values of the
    active vectors (the shared inner loop of both padding layouts)."""
    counts = nnz[active]
    idx_cat = np.concatenate([v.indices for v, a in zip(vecs, active) if a])
    val_cat = np.concatenate([v.values for v, a in zip(vecs, active) if a])
    rows = np.repeat(np.nonzero(active)[0], counts)
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    cols = np.arange(idx_cat.size) - np.repeat(starts, counts)
    return rows, cols, idx_cat, val_cat, counts


def _keys_i32(idx_cat: np.ndarray) -> np.ndarray:
    """Fold int64 indices into the kernels' uint32 key domain (as int32)."""
    return (idx_cat & np.int64(0xFFFFFFFF)).astype(np.uint32).astype(np.int32)


def padded_width(nnz: np.ndarray, bucket: int = 256) -> int:
    """The ``N`` of a padded ``[B, N]`` batch of vectors with ``nnz``
    non-zeros: the longest rounded up to a ``bucket`` multiple, at least
    one bucket."""
    longest = int(nnz.max()) if nnz.size else 0
    return max(bucket, -(-longest // bucket) * bucket)


def pad_sparse_batch(vecs: Sequence[SparseVec], *, bucket: int = 256
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Pad sparse vectors into the ICWS kernel's ``[B, N]`` layout.

    Returns host arrays ``(w, keys, vals, norms)``: f32 normalized squared
    weights, int32 keys (mod 2^32, the kernel's key domain), f32 normalized
    signed values, and f64 norms.  ``N`` is the max nnz rounded up to a
    multiple of ``bucket`` so repeated ingests reuse the same jit cache entry.

    The fill is one flat numpy scatter over the concatenated indices/values
    of the whole batch -- no per-vector Python loop.  Norms stay per-vector
    ``SparseVec.norm()`` calls so the normalized values are bitwise
    identical to the host sketcher's (``np.sum`` pairwise summation).
    """
    B = len(vecs)
    nnz = np.fromiter((v.nnz for v in vecs), np.int64, count=B)
    N = padded_width(nnz, bucket)
    w = np.zeros((B, N), np.float32)
    keys = np.zeros((B, N), np.int32)
    vals = np.zeros((B, N), np.float32)
    norms = np.array([v.norm() for v in vecs], np.float64)
    active = (nnz > 0) & (norms > 0.0) if B else np.zeros(0, bool)
    if np.any(active):
        rows, cols, idx_cat, val_cat, counts = _flat_scatter(vecs, active, nnz)
        z32 = (val_cat / np.repeat(norms[active], counts)).astype(np.float32)
        w[rows, cols] = z32 * z32
        keys[rows, cols] = _keys_i32(idx_cat)
        vals[rows, cols] = z32
    return w, keys, vals, norms


def pad_linear_batch(vecs: Sequence[SparseVec], *, bucket: int = 256
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Pad sparse vectors into the linear kernels' ``[B, N]`` layout.

    Returns host arrays ``(keys, vals)``: int32 keys (mod 2^32) and f32 RAW
    signed values (linear sketches are applied to the un-normalized vector;
    there is no norm side-channel).  Padding lanes hold value 0, which
    contributes nothing to any linear sketch.
    """
    B = len(vecs)
    nnz = np.fromiter((v.nnz for v in vecs), np.int64, count=B)
    N = padded_width(nnz, bucket)
    keys = np.zeros((B, N), np.int32)
    vals = np.zeros((B, N), np.float32)
    active = nnz > 0 if B else np.zeros(0, bool)
    if np.any(active):
        rows, cols, idx_cat, val_cat, _ = _flat_scatter(vecs, active, nnz)
        keys[rows, cols] = _keys_i32(idx_cat)
        vals[rows, cols] = val_cat.astype(np.float32)
    return keys, vals


def pad_sample_batch(vecs: Sequence[SparseVec], *, slots: int,
                     method: str = "ts", seed: int = 0,
                     target: "int | None" = None
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Build fixed-slot sampling-sketch rows for a batch of sparse vectors.

    Returns host arrays ``(keys [B, slots] i32, vals [B, slots] f32,
    tau [B] f32)`` in the :mod:`repro.kernels.sample_estimate` layout:
    live (key, value) pairs ascending-key in the leading slots, empty slots
    filled with the query-pad sentinel (-1) and value 0 (probability 0
    under the kernel's epilogue, hence inert), and ``tau`` the per-row
    probability scale.  ``method`` picks the scheme (``"ts"`` threshold /
    ``"ps"`` priority); the row contents are byte-identical to what the
    :mod:`repro.core.sampling` host oracles store, so host-oracle estimates
    and device key-match estimates agree on the same vectors.

    Unlike the ICWS/linear pads this is not a scatter into a kernel input
    -- the sampling *is* the sketch, and it is selection-bound host work
    (per-vector hash + sort/top-k), not a device reduction.
    """
    if method == "ts":
        def select(v):
            return threshold_sample(v.indices, v.values, slots=slots,
                                    seed=seed, target=target)
    elif method == "ps":
        if target is not None:
            raise ValueError("target is a threshold-sampling knob")

        def select(v):
            return priority_sample(v.indices, v.values, slots=slots,
                                   seed=seed)
    else:
        raise ValueError(f"unknown sampling method {method!r}; "
                         "choose 'ts' or 'ps'")
    B = len(vecs)
    keys = np.full((B, slots), SAMPLE_QUERY_PAD_KEY, np.int32)
    vals = np.zeros((B, slots), np.float32)
    taus = np.zeros(B, np.float32)
    for b, v in enumerate(vecs):
        k, vv, tau = select(v)
        keys[b, :k.size] = k.astype(np.int32)
        vals[b, :k.size] = vv.astype(np.float32)
        taus[b] = tau
    return keys, vals, taus


def sketch_batch(vecs: Sequence[SparseVec], *, m: int, seed: int = 0,
                 bucket: int = 256):
    """Device-sketch a batch of sparse vectors through the Pallas ICWS kernel.

    Returns device arrays ``(fp [B, m] int32, val [B, m] f32, norm [B] f32,
    argkey [B, m] int32)`` -- the four ICWS family components; ``argkey``
    is the merge sidecar (winning index per sample).
    """
    w, keys, vals, norms = pad_sparse_batch(vecs, bucket=bucket)
    fp, val, _, argkey = ops.icws_sketch(jnp.asarray(w), jnp.asarray(keys),
                                         jnp.asarray(vals), m=m, seed=seed)
    return fp, val, jnp.asarray(norms, jnp.float32), argkey


def dmh_sketch_batch(vecs: Sequence[SparseVec], *, m: int, seed: int = 0,
                     bucket: int = 256):
    """Device-sketch a batch of sparse vectors through the Pallas DMH kernel.

    Same padded layout (:func:`pad_sparse_batch`) and the same four
    components as :func:`sketch_batch` -- only the kernel differs (one
    binning pass over the non-zeros instead of the m-way ICWS broadcast),
    so lake ingest swaps families with no layout change.

    For m > 64 each key is expanded into ``dmh_replication(m)``
    pseudo-key replicas before the launch (the host oracle
    :meth:`repro.core.dmh.DMH.sketch` expands identically through the
    shared :func:`repro.core.dmh.replicate_keys`); the kernel itself is
    replication-agnostic.  Pad lanes replicate inertly (w = 0 ranks to
    the +inf sentinel regardless of the pseudo-key).
    """
    w, keys, vals, norms = pad_sparse_batch(vecs, bucket=bucket)
    c = dmh_replication(m)
    if c > 1:
        keys = replicate_keys(keys.view(np.uint32), c).view(np.int32)
        w = np.tile(w, (1, c))
        vals = np.tile(vals, (1, c))
    fp, val, _, argkey = ops.dmh_sketch(jnp.asarray(w), jnp.asarray(keys),
                                        jnp.asarray(vals), m=m, seed=seed)
    return fp, val, jnp.asarray(norms, jnp.float32), argkey
