"""Pallas TPU kernel: batched JL (AMS) projection of padded sparse batches.

``proj[b, t] = (1/sqrt(m)) * sum_i sign(t, key_i) * val_i`` with +-1 signs
drawn per (sample t, key) from the shared u32 mixing RNG (stream 31 -- the
:class:`repro.core.linear.JLU32` host contract).  Each grid step hashes a
``[BR, BM, BN]`` (rows x samples x non-zeros) sign cross in VMEM and
reduces the signed values over the non-zero lanes -- f32 VPU work, one
hash per lane -- accumulating across the (sequential, innermost) N
dimension.  Zero-valued padding lanes contribute sign * 0 = 0, so padding
is inert with no sentinel machinery.

Blocks: ``BR`` = 8 rows (the TPU tiling rule's row block; pad rows are
all-zero and sliced off), ``BM`` = 128 samples, ``BN`` = 256 non-zeros:
one ``[8, 128, 256]`` f32 cross is 1 MiB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import JL_SIGN_STREAM, hash_u32, salt_for


def _jl_kernel(key_ref, val_ref, out_ref, *, seed: int, bm: int):
    m_idx = pl.program_id(1)
    n_idx = pl.program_id(2)

    keys = key_ref[:, :].astype(jnp.uint32)[:, None, :]      # [BR, 1, BN]
    vals = val_ref[:, :][:, None, :]                          # [BR, 1, BN]
    t = m_idx * bm + jax.lax.broadcasted_iota(jnp.int32, (1, bm, 1), 1)
    hs = hash_u32(keys, salt_for(seed, JL_SIGN_STREAM, t))   # [BR, BM, BN]
    tile = jnp.sum(jnp.where((hs & jnp.uint32(1)) == 0, vals, -vals),
                   axis=2)                                    # [BR, BM]

    @pl.when(n_idx == 0)
    def _init():
        out_ref[:, :] = tile

    @pl.when(n_idx != 0)
    def _acc():
        out_ref[:, :] = out_ref[:, :] + tile


@functools.partial(jax.jit, static_argnames=("m", "seed", "br", "bm", "bn",
                                             "interpret"))
def jl_sketch_pallas(keys, vals, *, m: int, seed: int = 0, br: int = 8,
                     bm: int = 128, bn: int = 256, interpret: bool = False):
    """JL projections [B, m] of a padded sparse batch.

    Args: keys [B, N] int32 vector indices (mod 2^32); vals [B, N] f32
    signed values, 0 marking padding.  Matches
    :func:`repro.kernels.ref.jl_sketch_ref`.
    """
    B, N = keys.shape
    n_pad = (-N) % bn
    b_pad = (-B) % br
    if n_pad or b_pad:
        keys = jnp.pad(keys, ((0, b_pad), (0, n_pad)))
        vals = jnp.pad(vals, ((0, b_pad), (0, n_pad)))  # zero values: inert
    m_padded = m + ((-m) % bm)
    grid = ((B + b_pad) // br, m_padded // bm, (N + n_pad) // bn)
    kernel = functools.partial(_jl_kernel, seed=seed, bm=bm)
    proj = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((br, bn), lambda b, mi, ni: (b, ni))] * 2,
        out_specs=pl.BlockSpec((br, bm), lambda b, mi, ni: (b, mi)),
        out_shape=jax.ShapeDtypeStruct((B + b_pad, m_padded), jnp.float32),
        interpret=interpret,
    )(keys.astype(jnp.int32), vals.astype(jnp.float32))
    return proj[:B, :m] / jnp.sqrt(jnp.float32(m))
