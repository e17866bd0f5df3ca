"""Pallas TPU kernels: CountSketch of dense vectors and padded sparse batches.

Formulated scatter-free: instead of a scatter (which TPUs hate), each grid
step compares the hashed buckets of a block of coordinates against an iota
of table lanes and reduces the signed values that land on each lane -- a
``[rows, BW, BT]`` one-hot select and an f32 lane reduction in VMEM.  The
table accumulates across the (sequential, innermost) coordinate dimension.

Two entry points share that formulation:

  * :func:`countsketch_pallas` -- dense vector (gradient compression);
    buckets/signs are hashed from the element's *position*.
  * :func:`countsketch_sparse_pallas` -- a ``[B, N]`` padded sparse batch
    (corpus/query ingest for the CS serving family), 8 rows per grid step;
    buckets/signs are hashed from the element's *key*, with the same salt
    streams, so a sparse vector sketched by key equals the dense kernel's
    sketch of its densification.  Zero-valued padding lanes contribute
    sign * 0 = 0 -- padding is inert with no sentinel machinery.

VMEM per step: the one-hot cross, ``BW x BT`` = 128 x 1024 (dense) or
``8 x BW x BN`` = 8 x 128 x 256 (sparse) f32, 0.5-1 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import (CS_BUCKET_STREAM, CS_SIGN_STREAM, hash_u32, salt_for)


def _lane_table(bucket, sign, vals, lanes):
    """``[R, BW]`` table contributions of ``[R, BT]`` coordinates: the
    signed values summed per bucket lane (``lanes`` is ``[1, BW, 1]``)."""
    contrib = (sign * vals.astype(jnp.float32))[:, None, :]  # [R, 1, BT]
    eq = bucket[:, None, :] == lanes                          # [R, BW, BT]
    return jnp.sum(jnp.where(eq, contrib, 0.0), axis=2)


def _signed_buckets(keys, seed: int, r, width: int):
    hb = hash_u32(keys, salt_for(seed, CS_BUCKET_STREAM, r))
    bucket = (hb % jnp.uint32(width)).astype(jnp.int32)
    hs = hash_u32(keys, salt_for(seed, CS_SIGN_STREAM, r))
    sign = jnp.where((hs & jnp.uint32(1)) == 0, 1.0, -1.0).astype(jnp.float32)
    return bucket, sign


def _cs_kernel(x_ref, out_ref, *, width: int, seed: int, bt: int, bw: int,
               offset: int):
    r_idx = pl.program_id(0)
    w_idx = pl.program_id(1)
    t_idx = pl.program_id(2)

    x = x_ref[:, :]                                           # [1, BT]
    idx = (jnp.uint32(offset) + (
        t_idx * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1))
        .astype(jnp.uint32))
    bucket, sign = _signed_buckets(idx, seed, r_idx * jnp.ones((), jnp.int32),
                                   width)
    lanes = w_idx * bw + jax.lax.broadcasted_iota(jnp.int32, (1, bw, 1), 1)
    tile = _lane_table(bucket, sign, x, lanes)                # [1, BW]

    @pl.when(t_idx == 0)
    def _init():
        out_ref[0, :, :] = tile

    @pl.when(t_idx != 0)
    def _acc():
        out_ref[0, :, :] = out_ref[0, :, :] + tile


@functools.partial(jax.jit, static_argnames=("width", "reps", "seed", "offset",
                                             "bt", "bw", "interpret"))
def countsketch_pallas(x, *, width: int, reps: int = 5, seed: int = 0,
                       offset: int = 0, bt: int = 1024, bw: int = 128,
                       interpret: bool = False):
    """CountSketch table [reps, width] of dense x [T].  Matches
    :func:`repro.kernels.ref.countsketch_ref`."""
    (T,) = x.shape
    t_pad = (-T) % bt
    if t_pad:
        x = jnp.pad(x, (0, t_pad))        # padded values are 0 => no contribution
    w_padded = width + ((-width) % bw)
    grid = (reps, w_padded // bw, (T + t_pad) // bt)
    kernel = functools.partial(_cs_kernel, width=width, seed=seed,
                               bt=bt, bw=bw, offset=offset)
    table = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, bt), lambda r, wi, ti: (0, ti))],
        out_specs=pl.BlockSpec((1, 1, bw), lambda r, wi, ti: (r, 0, wi)),
        out_shape=jax.ShapeDtypeStruct((reps, 1, w_padded), jnp.float32),
        interpret=interpret,
    )(x[None, :])
    return table[:, 0, :width]


def _cs_sparse_kernel(key_ref, val_ref, out_ref, *, width: int, seed: int,
                      bw: int):
    r_idx = pl.program_id(1)
    w_idx = pl.program_id(2)
    n_idx = pl.program_id(3)

    keys = key_ref[:, :].astype(jnp.uint32)                   # [BR, BN]
    bucket, sign = _signed_buckets(keys, seed, r_idx * jnp.ones((), jnp.int32),
                                   width)
    lanes = w_idx * bw + jax.lax.broadcasted_iota(jnp.int32, (1, bw, 1), 1)
    tile = _lane_table(bucket, sign, val_ref[:, :], lanes)    # [BR, BW]

    @pl.when(n_idx == 0)
    def _init():
        out_ref[0, :, :] = tile

    @pl.when(n_idx != 0)
    def _acc():
        out_ref[0, :, :] = out_ref[0, :, :] + tile


@functools.partial(jax.jit, static_argnames=("width", "reps", "seed", "br",
                                             "bn", "bw", "interpret"))
def countsketch_sparse_pallas(keys, vals, *, width: int, reps: int = 5,
                              seed: int = 0, br: int = 8, bn: int = 256,
                              bw: int = 128, interpret: bool = False):
    """CountSketch tables [B, reps, width] of a padded sparse batch.

    Args: keys [B, N] int32 vector indices (mod 2^32, the kernel key
    domain); vals [B, N] f32 signed values, 0 marking padding.  Matches
    :func:`repro.kernels.ref.countsketch_sparse_ref` and the host
    :class:`repro.core.linear.CountSketchU32` contract.  The kernel writes
    rep-major ``[reps, B, W]`` tables (row blocks of ``br``) and the
    wrapper transposes the small result.
    """
    B, N = keys.shape
    n_pad = (-N) % bn
    b_pad = (-B) % br
    if n_pad or b_pad:
        keys = jnp.pad(keys, ((0, b_pad), (0, n_pad)))
        vals = jnp.pad(vals, ((0, b_pad), (0, n_pad)))    # zero values: inert
    w_padded = width + ((-width) % bw)
    Bp = B + b_pad
    grid = (Bp // br, reps, w_padded // bw, (N + n_pad) // bn)
    kernel = functools.partial(_cs_sparse_kernel, width=width, seed=seed,
                               bw=bw)
    table = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, bn), lambda b, r, wi, ni: (b, ni))] * 2,
        out_specs=pl.BlockSpec((1, br, bw), lambda b, r, wi, ni: (r, b, wi)),
        out_shape=jax.ShapeDtypeStruct((reps, Bp, w_padded), jnp.float32),
        interpret=interpret,
    )(keys.astype(jnp.int32), vals.astype(jnp.float32))
    return jnp.swapaxes(table, 0, 1)[:B, :, :width]
