"""Pallas TPU kernel: densified one-permutation weighted MinHash ingest.

The ICWS sketch kernel (:mod:`repro.kernels.icws_sketch`) does O(N * m)
hash work per vector: 5 uniform draws per (non-zero x sample) lane.  DMH
(arXiv:1602.08393 with the optimal densification of arXiv:1703.04664, see
:mod:`repro.core.dmh`) needs O(N + m): each non-zero is binned into its
sample index by ONE u32 hash, scored by ICWS variates drawn at that single
t = bin, and each of the m bins keeps its minimum; empty bins then borrow
from occupied ones through a reseeded 2-universal probe sequence (uniform
borrowing, not the biased rotation).

Grid: ``(B/BR, N/BN)`` -- deliberately NO m grid dimension.  The whole
m-bin state ``[BR, BM]`` (BM = m rounded up to a lane multiple) stays
resident in VMEM across the sequential non-zero steps; that residency is
what converts the ICWS kernel's per-(lane x sample) hashing into per-lane
hashing.  Each step draws the 5 uniforms on the ``[BR, BN]`` lane tile,
masks one ``[BR, BM, BN]`` bin-equality cross for the per-bin argmin, and
min-merges winners into the running blocks with strict ``<`` (earlier
tiles win ties -- the oracle's first-index argmin order).  The per-bin
argmin is two min-reductions (the lowest lane attaining the bin minimum),
and the winner payloads (key / level / value) are one-hot reduced.

Densification runs after the kernel, as a jnp epilogue shared with the
scatter lowering (:func:`_densify`, probes chunked 128 wide to bound
temporaries): borrowing is a gather across bins, which XLA does natively;
``pack_vals=True`` packs the values after it, as for ICWS.  The output
wire layout is identical to ICWS -- ``(fp, val, amin, argkey)`` -- so
every estimate / packed / sharded launch consumes DMH rows unchanged.

VMEM budget per step (f32): inputs ``3 * BR*BN`` + outputs ``4 * BR*BM`` +
a few ``[BR, BM, BN]`` cross temporaries.  Results are bitwise independent
of BR and BN (global first-min per bin); BM only pads (inert bins, sliced
off) and the probe budget is a pure function of m
(:func:`repro.kernels.common.densify_probes`), never tuned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import (DMH_BETA_STREAM, DMH_BIN_STREAM, DMH_C1_STREAM,
                     DMH_C2_STREAM, DMH_DENSIFY_STREAM, DMH_FP_STREAM,
                     DMH_R1_STREAM, DMH_R2_STREAM, densify_probes, hash_u32,
                     salt_for, uniform01)
from .packed import pack_halfwords_f32
from .ref import BIG

_PROBE_CHUNK = 128   # probes materialized at once by the densification


def _densify(fp, val, amin, key, *, seed: int, m: int):
    """Fill empty bins from occupied ones (optimal densification), on
    ``[B, m]`` per-bin minima.

    Probes ``src = h(t; j) mod m`` for j = 0..J-1 (J = ``densify_probes(m)``,
    ``CHUNK`` probes materialized at a time); the first probe landing on an
    occupied bin is the borrow source.  If every probe misses, fall back to
    the first occupied bin (exact when exactly one bin is occupied;
    coordinated regardless).  Rows with no occupied bin at all are left
    untouched (the wrappers' empty fixup emits -1).  A gather-shaped jnp
    epilogue shared by the Pallas and scatter lowerings, so both densify
    identically.
    """
    occ = amin < BIG                                       # [B, m]
    jprobe = densify_probes(m)
    tu = jnp.arange(m, dtype=jnp.uint32)
    best_j = jnp.full(occ.shape, jprobe, jnp.int32)
    for j0 in range(0, jprobe, _PROBE_CHUNK):
        js = j0 + jnp.arange(_PROBE_CHUNK, dtype=jnp.int32)
        psalt = salt_for(seed, DMH_DENSIFY_STREAM, js)     # [CHUNK]
        src = (hash_u32(tu[:, None], psalt[None, :])
               % jnp.uint32(m)).astype(jnp.int32)          # [m, CHUNK]
        hit = jnp.take(occ, src, axis=1)                   # [B, m, CHUNK]
        found = jnp.any(hit, axis=2)
        firstj = j0 + jnp.argmax(hit, axis=2).astype(jnp.int32)
        best_j = jnp.where((best_j == jprobe) & found, firstj, best_j)
    has = best_j < jprobe
    salt_w = salt_for(seed, DMH_DENSIFY_STREAM, jnp.where(has, best_j, 0))
    src_w = (hash_u32(tu[None, :], salt_w)
             % jnp.uint32(m)).astype(jnp.int32)            # [B, m]
    fallback = jnp.argmax(occ, axis=1).astype(jnp.int32)[:, None]
    src_sel = jnp.where(has, src_w, fallback)
    need = (~occ) & jnp.any(occ, axis=1)[:, None]

    def borrow(x):
        return jnp.where(need, jnp.take_along_axis(x, src_sel, axis=1), x)

    return borrow(fp), borrow(val), borrow(amin), borrow(key)


def _finish(fp, val, amin, key, *, seed: int, m: int, pack_vals: bool):
    """Densify, then the empty-row fixup (and the optional value pack)
    shared by both lowerings."""
    fp, val, amin, key = _densify(fp, val, amin, key, seed=seed, m=m)
    empty = amin >= BIG
    outs = (jnp.where(empty, -1, fp), jnp.where(empty, 0.0, val), amin,
            jnp.where(empty, 0, key))
    if pack_vals:
        return outs + (pack_halfwords_f32(outs[1]),)
    return outs


def _dmh_kernel(w_ref, key_ref, val_ref, fp_ref, out_val_ref, amin_ref,
                out_key_ref, *, seed: int, m_live: int, bm: int, bn: int):
    n_idx = pl.program_id(1)

    w = w_ref[:, :]                                        # [BR, BN]
    keys = key_ref[:, :]                                   # [BR, BN] int32
    vals = val_ref[:, :]                                   # [BR, BN]
    br = w.shape[0]
    kk = keys.astype(jnp.uint32)

    bin_salt = salt_for(seed, DMH_BIN_STREAM, jnp.uint32(0))
    bins = (hash_u32(kk, bin_salt)
            % jnp.uint32(m_live)).astype(jnp.int32)        # [BR, BN]

    def u(stream):
        # variates at t = bin: one draw per LANE, not per (lane, sample)
        return uniform01(kk, salt_for(seed, stream, bins))

    r = -jnp.log(u(DMH_R1_STREAM) * u(DMH_R2_STREAM))
    c = -jnp.log(u(DMH_C1_STREAM) * u(DMH_C2_STREAM))
    beta = u(DMH_BETA_STREAM)
    logw = jnp.log(jnp.maximum(w, 1e-37))
    lvl = jnp.floor(logw / r + beta)
    y = jnp.exp(r * (lvl - beta))
    a = c / (y * jnp.exp(r))
    a = jnp.where(w > 0, a, BIG)                           # mask padding

    t3 = jax.lax.broadcasted_iota(jnp.int32, (1, bm, 1), 1)
    am = jnp.where(bins[:, None, :] == t3, a[:, None, :], BIG)  # [BR, BM, BN]
    # first-hit argmin per bin from two min-reductions, then one-hot
    # payload selection (at most one lane per bin is selected)
    amin = jnp.min(am, axis=2)                             # [BR, BM]
    cols = jax.lax.broadcasted_iota(jnp.int32, (br, bm, bn), 2)
    arg = jnp.min(jnp.where(am == amin[:, :, None], cols, bn), axis=2)
    sel = cols == arg[:, :, None]
    key_sel = jnp.sum(jnp.where(sel, keys[:, None, :], 0), axis=2)
    lvl_sel = jnp.sum(jnp.where(sel, lvl[:, None, :], 0.0), axis=2)
    val_sel = jnp.sum(jnp.where(sel, vals[:, None, :], 0.0), axis=2)

    t2 = jax.lax.broadcasted_iota(jnp.int32, (1, bm), 1)
    fpbits = hash_u32(
        key_sel.astype(jnp.uint32)
        ^ (lvl_sel.astype(jnp.int32).astype(jnp.uint32)
           * jnp.uint32(0x9E3779B9)),
        salt_for(seed, DMH_FP_STREAM, t2))
    # 31-bit fingerprint: non-negative int32 (see ref.dmh_sketch_ref)
    fp = (fpbits & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)

    @pl.when(n_idx == 0)
    def _init():
        amin_ref[:, :] = amin
        fp_ref[:, :] = fp
        out_val_ref[:, :] = val_sel
        out_key_ref[:, :] = key_sel

    @pl.when(n_idx != 0)
    def _merge():
        better = amin < amin_ref[:, :]
        amin_ref[:, :] = jnp.where(better, amin, amin_ref[:, :])
        fp_ref[:, :] = jnp.where(better, fp, fp_ref[:, :])
        out_val_ref[:, :] = jnp.where(better, val_sel, out_val_ref[:, :])
        out_key_ref[:, :] = jnp.where(better, key_sel, out_key_ref[:, :])


@functools.partial(jax.jit, static_argnames=("m", "seed", "br", "bm", "bn",
                                             "pack_vals", "interpret"))
def dmh_sketch_pallas(w, keys, vals, *, m: int, seed: int, br: int = 8,
                      bm: int = 128, bn: int = 256,
                      pack_vals: bool = False, interpret: bool = False):
    """Batched DMH sketch via Pallas.  See :func:`repro.kernels.ref.dmh_sketch_ref`.

    Args: w/keys/vals [B, N] (padded here to ``br``/``bn`` multiples);
    returns (fp [B, m] int32, val [B, m] f32, amin [B, m] f32, argkey
    [B, m] int32) -- the ICWS wire layout; borrowed (densified) bins carry
    their source bin's payload, and ``argkey`` doubles as the occupancy
    witness the merge path recovers origins from.  ``bm`` must cover m in
    one block (the bin state is VMEM-resident; there is no m grid axis);
    results are bitwise identical for every (br, bm, bn) choice.

    With ``pack_vals=True`` a fifth output is appended: the ``[B, m]``
    bf16 packed value plane, ``pack_halfwords_f32`` of the ``val`` output.
    """
    B, N = w.shape
    if bm % 128 or bm < m:
        raise ValueError(f"bm must be a lane multiple covering m; "
                         f"got bm={bm}, m={m}")
    n_pad = (-N) % bn
    b_pad = (-B) % br
    if n_pad or b_pad:
        w = jnp.pad(w, ((0, b_pad), (0, n_pad)))
        keys = jnp.pad(keys, ((0, b_pad), (0, n_pad)))
        vals = jnp.pad(vals, ((0, b_pad), (0, n_pad)))
    Bp, Np = w.shape

    kernel = functools.partial(_dmh_kernel, seed=seed, m_live=m, bm=bm,
                               bn=bn)
    fp, val, amin, key = pl.pallas_call(
        kernel,
        grid=(Bp // br, Np // bn),
        in_specs=[pl.BlockSpec((br, bn), lambda b, ni: (b, ni))] * 3,
        out_specs=[pl.BlockSpec((br, bm), lambda b, ni: (b, 0))] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((Bp, bm), jnp.int32),
            jax.ShapeDtypeStruct((Bp, bm), jnp.float32),
            jax.ShapeDtypeStruct((Bp, bm), jnp.float32),
            jax.ShapeDtypeStruct((Bp, bm), jnp.int32),
        ],
        interpret=interpret,
    )(w.astype(jnp.float32), keys.astype(jnp.int32),
      vals.astype(jnp.float32))

    return _finish(fp[:B, :m], val[:B, :m], amin[:B, :m], key[:B, :m],
                   seed=seed, m=m, pack_vals=pack_vals)


@functools.partial(jax.jit, static_argnames=("m", "seed", "pack_vals"))
def dmh_sketch_scatter(w, keys, vals, *, m: int, seed: int,
                       pack_vals: bool = False):
    """Scatter-min lowering of the DMH sketch -- same contract, O(nnz + m).

    The Pallas kernel realizes the per-bin argmin as a ``[BR, BM, BN]``
    bin-equality cross because TPU Pallas has no scatter primitive; the
    VPU evaluates that cross across its 8x128 lanes essentially for free,
    but interpret mode (and any non-TPU backend) must materialize it --
    re-introducing the O(nnz * m) work DMH exists to avoid.  This jnp
    builder is the genuinely linear form of the SAME computation: one
    XLA ``scatter-min`` per bin plane instead of the broadcast, winner =
    minimum ``a`` per bin with ties to the lowest lane index, which is
    exactly the kernel's strict-< tile order and the oracle's first-hit
    argmin.  Outputs match :func:`dmh_sketch_pallas` plane for plane
    (fingerprints / argkeys bitwise; ``val``/``amin`` to transcendental
    rounding); :mod:`repro.kernels.ops` dispatches here exactly where it
    would have forced ``interpret=True`` on the kernel.
    """
    B, N = w.shape
    w = w.astype(jnp.float32)
    vals = vals.astype(jnp.float32)
    kk = keys.astype(jnp.uint32)
    bins = (hash_u32(kk, salt_for(seed, DMH_BIN_STREAM, jnp.uint32(0)))
            % jnp.uint32(m)).astype(jnp.int32)                # [B, N]

    def u(stream):
        return uniform01(kk, salt_for(seed, stream, bins))

    r = -jnp.log(u(DMH_R1_STREAM) * u(DMH_R2_STREAM))
    c = -jnp.log(u(DMH_C1_STREAM) * u(DMH_C2_STREAM))
    beta = u(DMH_BETA_STREAM)
    logw = jnp.log(jnp.maximum(w, 1e-37))
    lvl = jnp.floor(logw / r + beta)
    y = jnp.exp(r * (lvl - beta))
    a = jnp.where(w > 0, c / (y * jnp.exp(r)), BIG).astype(jnp.float32)

    # per-bin first-min via two scatter-mins: the min itself, then the
    # lowest lane index attaining it (ties break like np.argmin)
    seg = (jnp.arange(B, dtype=jnp.int32)[:, None] * m + bins).reshape(-1)
    amin = (jnp.full(B * m, BIG, jnp.float32).at[seg].min(a.reshape(-1))
            .reshape(B, m))
    hit = a == jnp.take(amin.reshape(-1), seg).reshape(B, N)
    lane = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32), (B, N))
    arg = (jnp.full(B * m, N, jnp.int32).at[seg]
           .min(jnp.where(hit, lane, N).reshape(-1)).reshape(B, m))
    arg = jnp.minimum(arg, N - 1)     # bins no lane mapped to: inert gather

    key_sel = jnp.take_along_axis(keys.astype(jnp.int32), arg, axis=1)
    lvl_sel = jnp.take_along_axis(lvl, arg, axis=1)
    val_sel = jnp.take_along_axis(vals, arg, axis=1)
    t = jnp.arange(m, dtype=jnp.int32)
    fpbits = hash_u32(
        key_sel.astype(jnp.uint32)
        ^ (lvl_sel.astype(jnp.int32).astype(jnp.uint32)
           * jnp.uint32(0x9E3779B9)),
        salt_for(seed, DMH_FP_STREAM, t)[None, :])
    fp = (fpbits & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)

    return _finish(fp, val_sel, amin, key_sel, seed=seed, m=m,
                   pack_vals=pack_vals)
