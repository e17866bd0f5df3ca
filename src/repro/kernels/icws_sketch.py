"""Pallas TPU kernel: batched ICWS (weighted MinHash) sketching.

Grid: ``(B/BR, M/BM, N/BN)`` with the non-zero dimension N innermost and
*sequential* ("arbitrary"): each step computes ICWS hash values for a
``[BR, BM, BN]`` tile of (rows x samples x non-zeros) entirely in VMEM -- 5
uniform draws, two logs, one exp, one divide per lane, then a per-row argmin
-- and merges the tile winner into the running ``[BR, BM]`` output blocks
(value / fingerprint / min) with a strict ``<`` so earlier tiles win ties,
matching ``jnp.argmin`` first-hit semantics in the oracle.

``BR`` (row block, default 8) amortizes per-step costs across sketch rows
and is what the TPU tiling rule asks of the row dim: a single query's 3
field rows pad to one 8-row block, and the batched serving/ingest paths
launch 3Q-row batches.  Results are bitwise independent of all three block
sizes (each row's winner is a global min with first-index ties; the argmin
is two min-reductions, the lowest column attaining the minimum).

VMEM budget per step (f32): inputs ``3 * BR*BN`` + intermediates
``~6 * BR*BM*BN``.  At BR=8, BM=128, BN=256 one intermediate is 1 MiB,
inside the v5e's scoped VMEM.  The lane dimension (BN=256) is a multiple of
128 as the VPU wants; there is no MXU work in this kernel -- it is
VPU/transcendental bound, which is exactly why it beats the paper's
scalar "active index" loop on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import (ICWS_BETA_STREAM, ICWS_C1_STREAM, ICWS_C2_STREAM,
                     ICWS_FP_STREAM, ICWS_R1_STREAM, ICWS_R2_STREAM,
                     hash_u32, salt_for, uniform01)
from .packed import pack_halfwords_f32
from .ref import BIG


def _icws_kernel(w_ref, key_ref, val_ref, fp_ref, out_val_ref, amin_ref,
                 out_key_ref, *, seed: int, bm: int, bn: int):
    m_idx = pl.program_id(1)
    n_idx = pl.program_id(2)

    w = w_ref[:, :]                                   # [BR, BN]
    keys = key_ref[:, :]                              # [BR, BN] int32
    vals = val_ref[:, :]                              # [BR, BN]
    br = w.shape[0]

    # global sample ids, as a [1, BM, 1] column and a [1, BM] row
    t3 = m_idx * bm + jax.lax.broadcasted_iota(jnp.int32, (1, bm, 1), 1)
    t2 = m_idx * bm + jax.lax.broadcasted_iota(jnp.int32, (1, bm), 1)
    kk = keys.astype(jnp.uint32)[:, None, :]          # [BR, 1, BN]

    def u(stream):
        return uniform01(kk, salt_for(seed, stream, t3))   # [BR, BM, BN]

    r = -jnp.log(u(ICWS_R1_STREAM) * u(ICWS_R2_STREAM))
    c = -jnp.log(u(ICWS_C1_STREAM) * u(ICWS_C2_STREAM))
    beta = u(ICWS_BETA_STREAM)
    logw = jnp.log(jnp.maximum(w, 1e-37))[:, None, :]
    lvl = jnp.floor(logw / r + beta)
    y = jnp.exp(r * (lvl - beta))
    a = c / (y * jnp.exp(r))
    a = jnp.where((w > 0)[:, None, :], a, BIG)        # mask padding

    # first-hit argmin over the lane axis from two min-reductions: the
    # lowest column attaining the row minimum
    amin = jnp.min(a, axis=2)                         # [BR, BM]
    cols = jax.lax.broadcasted_iota(jnp.int32, (br, bm, bn), 2)
    arg = jnp.min(jnp.where(a == amin[:, :, None], cols, bn), axis=2)
    sel = cols == arg[:, :, None]                     # one-hot [BR, BM, BN]
    key_sel = jnp.sum(jnp.where(sel, keys[:, None, :], 0), axis=2)
    lvl_sel = jnp.sum(jnp.where(sel, lvl, 0.0), axis=2)
    val_sel = jnp.sum(jnp.where(sel, vals[:, None, :], 0.0), axis=2)

    fpbits = hash_u32(
        key_sel.astype(jnp.uint32)
        ^ (lvl_sel.astype(jnp.int32).astype(jnp.uint32) * jnp.uint32(0x9E3779B9)),
        salt_for(seed, ICWS_FP_STREAM, t2))
    # 31-bit fingerprint: non-negative int32 (see ref.icws_sketch_ref)
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
def icws_sketch_pallas(w, keys, vals, *, m: int, seed: int, br: int = 8,
                       bm: int = 128, bn: int = 256,
                       pack_vals: bool = False, interpret: bool = False):
    """Batched ICWS sketch via Pallas.  See :func:`repro.kernels.ref.icws_sketch_ref`.

    Args: w/keys/vals [B, N] (N padded to a multiple of ``bn`` by the caller
    or here); returns (fp [B, m] int32, val [B, m] f32, amin [B, m] f32,
    argkey [B, m] int32 -- the original vector index that won each sample,
    the sidecar the merge path re-levels from; 0 for empty inputs).
    ``br`` rows are sketched per grid step (pad rows are all-zero => empty);
    results are bitwise identical for every (br, bm, bn) choice.  On a TPU
    ``br`` must be a multiple of 8 and ``bm``/``bn`` of 128 (the block
    tiling rule).

    With ``pack_vals=True`` a fifth output is appended: the ``[B, m]``
    bf16 packed value plane, ``pack_halfwords_f32`` of the ``val`` output
    (an elementwise epilogue on the device, after the kernel).
    """
    B, N = w.shape
    n_pad = (-N) % bn
    b_pad = (-B) % br
    if n_pad or b_pad:
        w = jnp.pad(w, ((0, b_pad), (0, n_pad)))
        keys = jnp.pad(keys, ((0, b_pad), (0, n_pad)))
        vals = jnp.pad(vals, ((0, b_pad), (0, n_pad)))
    m_pad = (-m) % bm
    mp = m + m_pad
    Bp, Np = w.shape

    kernel = functools.partial(_icws_kernel, seed=seed, bm=bm, bn=bn)
    fp, val, amin, key = pl.pallas_call(
        kernel,
        grid=(Bp // br, mp // bm, Np // bn),
        in_specs=[pl.BlockSpec((br, bn), lambda b, mi, ni: (b, ni))] * 3,
        out_specs=[pl.BlockSpec((br, bm), lambda b, mi, ni: (b, mi))] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((Bp, mp), jnp.int32),
            jax.ShapeDtypeStruct((Bp, mp), jnp.float32),
            jax.ShapeDtypeStruct((Bp, mp), jnp.float32),
            jax.ShapeDtypeStruct((Bp, mp), jnp.int32),
        ],
        interpret=interpret,
    )(w.astype(jnp.float32), keys.astype(jnp.int32),
      vals.astype(jnp.float32))

    fp, val, amin, key = fp[:B, :m], val[:B, :m], amin[:B, :m], key[:B, :m]
    empty = amin >= BIG
    outs = (jnp.where(empty, -1, fp), jnp.where(empty, 0.0, val), amin,
            jnp.where(empty, 0, key))
    if pack_vals:
        return outs + (pack_halfwords_f32(outs[1]),)
    return outs
