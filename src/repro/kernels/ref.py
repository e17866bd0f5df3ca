"""Pure-jnp oracles for every Pallas kernel in this package.

Each function computes exactly what the corresponding kernel computes
(same RNG from :mod:`repro.kernels.common`, same masking, same reduction
order semantics where it matters), with no tiling.  Tests assert
``allclose(kernel(interpret=True), ref)`` across shape/dtype sweeps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .common import (CS_BUCKET_STREAM, CS_SIGN_STREAM, DMH_BETA_STREAM,
                     DMH_BIN_STREAM, DMH_C1_STREAM, DMH_C2_STREAM,
                     DMH_DENSIFY_STREAM, DMH_FP_STREAM, DMH_R1_STREAM,
                     DMH_R2_STREAM, ICWS_BETA_STREAM, ICWS_C1_STREAM,
                     ICWS_C2_STREAM, ICWS_FP_STREAM, ICWS_R1_STREAM,
                     ICWS_R2_STREAM, JL_SIGN_STREAM, densify_probes,
                     hash_u32, salt_for, uniform01)

BIG = 3.0e38  # python float: safe to close over in kernel bodies


# ---------------------------------------------------------------------------
# ICWS sketch  (Ioffe Consistent Weighted Sampling; see repro.core.icws)
# ---------------------------------------------------------------------------
def icws_sketch_ref(w, keys, vals, m: int, seed: int):
    """Reference ICWS sketch of a batch of padded sparse vectors.

    Args:
      w:    [B, N] f32 weights (normalized squared values); 0 => padding.
      keys: [B, N] int32 original vector indices (ignored where w == 0).
      vals: [B, N] f32 signed normalized values.
      m:    number of samples.
      seed: RNG seed.
    Returns:
      fp   [B, m] int32 fingerprints of (key, level, t); -1 for empty inputs,
      val  [B, m] f32 sampled signed values,
      amin [B, m] f32 the minimizing ICWS hash values,
      argkey [B, m] int32 winning original indices (0 for empty inputs) --
      the sidecar that lets the merge path re-level samples under a new norm.
    """
    B, N = w.shape
    t = jnp.arange(m, dtype=jnp.int32)                       # [m]
    kk = keys.astype(jnp.uint32)[:, None, :]                 # [B, 1, N]

    def u(stream):
        salt = salt_for(seed, stream, t)[None, :, None]      # [1, m, 1]
        return uniform01(kk, salt)                           # [B, m, N]

    r = -jnp.log(u(ICWS_R1_STREAM) * u(ICWS_R2_STREAM))
    c = -jnp.log(u(ICWS_C1_STREAM) * u(ICWS_C2_STREAM))
    beta = u(ICWS_BETA_STREAM)
    logw = jnp.log(jnp.maximum(w, 1e-37))[:, None, :]        # [B, 1, N]
    lvl = jnp.floor(logw / r + beta)
    y = jnp.exp(r * (lvl - beta))
    a = c / (y * jnp.exp(r))
    mask = (w > 0)[:, None, :]
    a = jnp.where(mask, a, BIG)

    arg = jnp.argmin(a, axis=2)                              # [B, m]
    amin = jnp.take_along_axis(a, arg[:, :, None], axis=2)[:, :, 0]
    key_sel = jnp.take_along_axis(keys, arg.astype(jnp.int32), axis=1)  # [B, m]
    lvl_sel = jnp.take_along_axis(lvl, arg[:, :, None], axis=2)[:, :, 0]
    val_sel = jnp.take_along_axis(vals, arg.astype(jnp.int32), axis=1)

    fpbits = hash_u32(
        key_sel.astype(jnp.uint32)
        ^ (lvl_sel.astype(jnp.int32).astype(jnp.uint32) * jnp.uint32(0x9E3779B9)),
        salt_for(seed, ICWS_FP_STREAM, t)[None, :])
    # 31-bit fingerprint: keeps int32 values non-negative so the estimator's
    # `fp >= 0` empty-sentinel guard never discards real collisions
    fp = (fpbits & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)
    nonempty = jnp.any(w > 0, axis=1)[:, None]
    fp = jnp.where(nonempty, fp, -1)
    val_sel = jnp.where(nonempty, val_sel, 0.0)
    key_sel = jnp.where(nonempty, key_sel, 0)
    return fp, val_sel, jnp.where(nonempty, amin, BIG), key_sel


# ---------------------------------------------------------------------------
# DMH sketch  (densified one-permutation weighted MinHash; repro.core.dmh)
# ---------------------------------------------------------------------------
def dmh_sketch_ref(w, keys, vals, m: int, seed: int):
    """Reference DMH sketch of a batch of padded sparse vectors.

    Args / returns exactly as :func:`icws_sketch_ref` (same wire layout),
    but each non-zero is binned into one sample ``t = h(key) mod m`` and
    scored by ICWS variates drawn at that single t; empty bins borrow from
    occupied ones through the reseeded densification probes.  ``amin`` of
    a borrowed bin is its source bin's minimum (< BIG marks it live).
    """
    B, N = w.shape
    kk = keys.astype(jnp.uint32)
    bin_salt = salt_for(seed, DMH_BIN_STREAM, jnp.uint32(0))
    bins = (hash_u32(kk, bin_salt) % jnp.uint32(m)).astype(jnp.int32)

    def u(stream):
        return uniform01(kk, salt_for(seed, stream, bins))    # [B, N]

    r = -jnp.log(u(DMH_R1_STREAM) * u(DMH_R2_STREAM))
    c = -jnp.log(u(DMH_C1_STREAM) * u(DMH_C2_STREAM))
    beta = u(DMH_BETA_STREAM)
    logw = jnp.log(jnp.maximum(w, 1e-37))
    lvl = jnp.floor(logw / r + beta)
    y = jnp.exp(r * (lvl - beta))
    a = c / (y * jnp.exp(r))
    a = jnp.where(w > 0, a, BIG)

    t = jnp.arange(m, dtype=jnp.int32)
    am = jnp.where(bins[:, None, :] == t[None, :, None],
                   a[:, None, :], BIG)                        # [B, m, N]
    arg = jnp.argmin(am, axis=2)                              # [B, m]
    amin = jnp.min(am, axis=2)
    key_sel = jnp.take_along_axis(keys, arg, axis=1)
    lvl_sel = jnp.take_along_axis(lvl, arg, axis=1)
    val_sel = jnp.take_along_axis(vals, arg, axis=1)

    fpbits = hash_u32(
        key_sel.astype(jnp.uint32)
        ^ (lvl_sel.astype(jnp.int32).astype(jnp.uint32)
           * jnp.uint32(0x9E3779B9)),
        salt_for(seed, DMH_FP_STREAM, t)[None, :])
    fp = (fpbits & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)

    # densification: first probe h(t; j) mod m landing on an occupied bin;
    # all-miss falls back to the first occupied bin (repro.core.dmh)
    occ = amin < BIG                                          # [B, m]
    J = densify_probes(m)
    js = jnp.arange(J, dtype=jnp.int32)
    psalt = salt_for(seed, DMH_DENSIFY_STREAM, js)
    src = (hash_u32(t[:, None].astype(jnp.uint32), psalt[None, :])
           % jnp.uint32(m)).astype(jnp.int32)                 # [m, J]
    occ_p = jnp.take(occ, src, axis=1)                        # [B, m, J]
    has = jnp.any(occ_p, axis=2)
    firstj = jnp.argmax(occ_p, axis=2).astype(jnp.int32)
    src_w = (hash_u32(t.astype(jnp.uint32),
                      salt_for(seed, DMH_DENSIFY_STREAM, firstj))
             % jnp.uint32(m)).astype(jnp.int32)               # [B, m]
    fallback = jnp.argmax(occ, axis=1).astype(jnp.int32)[:, None]
    src_sel = jnp.where(has, src_w, fallback)
    need = (~occ) & jnp.any(occ, axis=1)[:, None]

    def borrow(x):
        return jnp.where(need, jnp.take_along_axis(x, src_sel, axis=1), x)

    fp, val_sel, key_sel, amin = (borrow(fp), borrow(val_sel),
                                  borrow(key_sel), borrow(amin))
    alive = amin < BIG
    return (jnp.where(alive, fp, -1), jnp.where(alive, val_sel, 0.0),
            amin, jnp.where(alive, key_sel, 0))


# ---------------------------------------------------------------------------
# CountSketch  (linear sketch used for gradient compression)
# ---------------------------------------------------------------------------
def countsketch_ref(x, width: int, reps: int, seed: int, offset: int = 0):
    """Reference CountSketch of a dense f32 vector.

    Args:
      x:      [T] f32 values; element i has global index offset + i.
      width:  table width W.
      reps:   number of independent repetitions R.
      seed:   RNG seed.
    Returns: [R, W] f32 table.
    """
    (T,) = x.shape
    idx = (jnp.arange(T, dtype=jnp.uint32) + jnp.uint32(offset))
    r = jnp.arange(reps, dtype=jnp.int32)
    hb = hash_u32(idx[None, :], salt_for(seed, CS_BUCKET_STREAM, r)[:, None])      # [R, T]
    bucket = (hb % jnp.uint32(width)).astype(jnp.int32)
    hs = hash_u32(idx[None, :], salt_for(seed, CS_SIGN_STREAM, r)[:, None])
    sign = jnp.where((hs & jnp.uint32(1)) == 0, 1.0, -1.0).astype(x.dtype)
    contrib = sign * x[None, :]                                      # [R, T]
    onehot = jax.nn.one_hot(bucket, width, dtype=x.dtype)            # [R, T, W]
    return jnp.einsum("rt,rtw->rw", contrib, onehot).astype(jnp.float32)


def countsketch_sparse_ref(keys, vals, width: int, reps: int, seed: int):
    """Reference CountSketch of a padded sparse batch.

    Args:
      keys: [B, N] int32 vector indices (kernel key domain, mod 2^32).
      vals: [B, N] f32 signed values; 0 => padding (zero contribution, so
        padding is inert without any sentinel).
    Returns: [B, R, W] f32 tables.  Streams match :func:`countsketch_ref`,
    so sketching a densified vector by position gives the same table.
    """
    idx = keys.astype(jnp.uint32)                                    # [B, N]
    r = jnp.arange(reps, dtype=jnp.int32)
    hb = hash_u32(idx[:, None, :], salt_for(seed, CS_BUCKET_STREAM, r)[None, :, None])
    bucket = (hb % jnp.uint32(width)).astype(jnp.int32)              # [B, R, N]
    hs = hash_u32(idx[:, None, :], salt_for(seed, CS_SIGN_STREAM, r)[None, :, None])
    sign = jnp.where((hs & jnp.uint32(1)) == 0, 1.0, -1.0).astype(jnp.float32)
    contrib = sign * vals.astype(jnp.float32)[:, None, :]            # [B, R, N]
    onehot = jax.nn.one_hot(bucket, width, dtype=jnp.float32)        # [B, R, N, W]
    return jnp.einsum("brn,brnw->brw", contrib, onehot)


def jl_sketch_ref(keys, vals, m: int, seed: int):
    """Reference JL projection of a padded sparse batch.

    Args as :func:`countsketch_sparse_ref`; returns [B, m] f32 projections
    ``proj[t] = (1/sqrt(m)) * sum_i sign(t, key_i) * val_i`` with signs from
    u32 stream 31 (the :class:`repro.core.linear.JLU32` contract).
    """
    t = jnp.arange(m, dtype=jnp.int32)
    hs = hash_u32(keys.astype(jnp.uint32)[:, None, :],
                  salt_for(seed, JL_SIGN_STREAM, t)[None, :, None])              # [B, m, N]
    sign = jnp.where((hs & jnp.uint32(1)) == 0, 1.0, -1.0).astype(jnp.float32)
    proj = jnp.einsum("bmn,bn->bm", sign, vals.astype(jnp.float32))
    return proj / jnp.sqrt(jnp.float32(m))


def countsketch_decode_ref(table, indices, seed: int):
    """Median-of-reps unbiased point query (decompression)."""
    reps, width = table.shape
    r = jnp.arange(reps, dtype=jnp.int32)
    idx = indices.astype(jnp.uint32)
    hb = hash_u32(idx[None, :], salt_for(seed, CS_BUCKET_STREAM, r)[:, None])
    bucket = (hb % jnp.uint32(width)).astype(jnp.int32)
    hs = hash_u32(idx[None, :], salt_for(seed, CS_SIGN_STREAM, r)[:, None])
    sign = jnp.where((hs & jnp.uint32(1)) == 0, 1.0, -1.0)
    est = jnp.take_along_axis(table, bucket, axis=1) * sign          # [R, n]
    return jnp.median(est, axis=0)


# ---------------------------------------------------------------------------
# Fused sketch-pair estimator (Algorithm 5 inner loop over m samples)
# ---------------------------------------------------------------------------
def estimate_many_vs_many_ref(fq, vq, fpc, vc):
    """Per-pair partial sums for the WMH/ICWS estimator, Q query sketches
    vs a P-row corpus.

    Args:  fq/vq [Q, m] int32 fingerprints / f32 values; fpc/vc [P, m].
    Returns:
      n_collide [Q, P] f32 -- number of colliding samples,
      s_weight  [Q, P] f32 -- sum of vq*vc / min(vq^2, vc^2) over collisions.
    The oracle may materialize the [Q, P, m] broadcast; the kernel must not.
    """
    fqb, fcb = fq[:, None, :], fpc[None, :, :]
    vqb, vcb = vq[:, None, :], vc[None, :, :]
    collide = (fqb == fcb) & (fqb >= 0)
    q = jnp.minimum(vqb * vqb, vcb * vcb)
    safe_q = jnp.where(collide & (q > 0), q, 1.0)
    term = jnp.where(collide, vqb * vcb / safe_q, 0.0)
    return collide.astype(jnp.float32).sum(axis=2), term.sum(axis=2)


@functools.partial(jax.jit, static_argnames=("qmap", "cmap"))
def estimate_fields_ref(fq, vq, fpc, vc, *, qmap, cmap):
    """Fused multi-field many-vs-many partials.

    Args:  fq/vq [F, Q, m] per-field queries; fpc/vc [C, P, m] per-field
    corpus; qmap/cmap length-G field-index tuples (see the kernel).
    Returns (n_collide [G, Q, P], s_weight [G, Q, P]).  Jitted: one
    compile a shape instead of one dispatch per op.
    """
    cnts, sws = [], []
    for qf, cf in zip(qmap, cmap):
        cnt, sw = estimate_many_vs_many_ref(fq[qf], vq[qf], fpc[cf], vc[cf])
        cnts.append(cnt)
        sws.append(sw)
    return jnp.stack(cnts), jnp.stack(sws)


# ---------------------------------------------------------------------------
# Sampling-family estimation: unaligned key-match contraction (TS/PS)
# ---------------------------------------------------------------------------
def sample_estimate_fields_ref(kq, vq, aq, kc, vc, ac, *, qmap, cmap):
    """Fused multi-field key-match estimates for sampling sketches.

    Args:  kq/vq/aq [F, Q, m] per-field query sample keys / values /
    inclusion probabilities (:func:`repro.kernels.sample_estimate.
    sample_inclusion_probs`); kc/vc/ac [C, P, m] per-field corpus samples;
    qmap/cmap length-G field-index tuples (as the ICWS fields kernel).
    Returns [G, Q, P] f32 estimates.  The oracle may materialize the
    [Q, P, m, m] key-equality cross; the kernel must not.
    """
    outs = []
    for qf, cf in zip(qmap, cmap):
        kqb, kcb = kq[qf][:, None, :, None], kc[cf][None, :, None, :]
        p = jnp.minimum(aq[qf][:, None, :, None], ac[cf][None, :, None, :])
        live = (kqb == kcb) & (kqb >= 0) & (p > 0)
        term = jnp.where(
            live,
            vq[qf][:, None, :, None] * vc[cf][None, :, None, :]
            / jnp.where(live, p, 1.0), 0.0)
        outs.append(term.sum(axis=(2, 3)))
    return jnp.stack(outs)


# ---------------------------------------------------------------------------
# Linear-family estimation: per-rep sketch dot products (MXU work on device)
# ---------------------------------------------------------------------------
def linear_estimate_fields_ref(tq, tc, *, qmap, cmap):
    """Fused multi-field per-rep dot products for linear sketches.

    Args:  tq [F, Q, R, W] per-field query tables; tc [C, P, R, W] per-field
    corpus tables; qmap/cmap length-G field-index tuples (as the ICWS
    fields kernel).  JL is the R = 1, W = m case.
    Returns [G, R, Q, P] f32 per-rep inner products
    ``out[g, r, q, p] = <tq[qmap[g], q, r], tc[cmap[g], p, r]>`` -- the
    median-of-reps (CS) or squeeze (JL) epilogue happens in the ops layer.
    """
    return jnp.stack([
        jnp.einsum("qrw,prw->rqp", tq[qf].astype(jnp.float32),
                   tc[cf].astype(jnp.float32))
        for qf, cf in zip(qmap, cmap)])
