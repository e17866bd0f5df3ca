"""Pallas TPU kernel: fused flash attention (forward).

Motivation (measured, see EXPERIMENTS.md §Perf): the XLA chunked-attention
path materializes each [qc, kc] f32 score tile in HBM ~6-8 times across the
softmax chain (sub/exp/max/select fusions) -- 2.6 TB/device/step on Mixtral
train_4k, the dominant memory-roofline term on every dense train/prefill
cell.  This kernel keeps the whole online-softmax recurrence in VMEM: HBM
traffic collapses to one read of q/k/v + one write of o per tile.

Layout: q [BH, T, D], kv [BKV, S, D] with GQA handled zero-copy by the
index map (q head bh reads kv head bh // group).  Grid (BH, nq); the key
loop runs inside the kernel over S/kc slices with (m, l, acc) carried in
registers/VMEM.  VMEM budget: kv block 2*S*D bf16 (32k x 128 => 8 MiB) +
qc*D accumulators -- fits v5e's ~16 MiB budget up to S=32k at D=128, with
kc-slicing keeping the working set far smaller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, kc: int, causal: bool,
                  window: int, scale: float, q_offset: int, k_offset: int):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale              # [qc, D]
    qc, D = q.shape
    S = k_ref.shape[1]
    nk = S // kc
    q_pos = q_offset + qi * qc + jax.lax.iota(jnp.int32, qc)

    def body(i, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.dslice(i * kc, kc), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.dslice(i * kc, kc), :].astype(jnp.float32)
        s = q @ k_blk.T                                    # [qc, kc]
        k_pos = k_offset + i * kc + jax.lax.iota(jnp.int32, kc)
        mask = jnp.ones((qc, kc), jnp.bool_)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask, s, NEG)
        m_new = jnp.maximum(m, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=1)
        acc_new = acc * corr[:, None] + p @ v_blk
        return m_new, l_new, acc_new

    m0 = jnp.full((qc,), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((qc,), jnp.float32)
    a0 = jnp.zeros((qc, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nk, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "group", "causal", "window", "qc", "kc", "q_offset", "k_offset",
    "interpret"))
def flash_attention_pallas(q, k, v, *, group: int = 1, causal: bool = True,
                           window: int = 0, qc: int = 512, kc: int = 512,
                           q_offset: int = 0, k_offset: int = 0,
                           interpret: bool = False):
    """q [BH, T, D]; k/v [BH//group, S, D].  Returns o [BH, T, D].

    ``group`` = GQA group size: q head i attends kv head i // group via the
    BlockSpec index map (no kv repetition in memory).
    """
    BH, T, D = q.shape
    S = k.shape[1]
    qc = min(qc, T)
    kc = min(kc, S)
    assert T % qc == 0 and S % kc == 0, (T, qc, S, kc)
    grid = (BH, T // qc)
    scale = 1.0 / (D ** 0.5)
    kernel = functools.partial(_flash_kernel, kc=kc, causal=causal,
                               window=window, scale=scale,
                               q_offset=q_offset, k_offset=k_offset)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, qc, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, S, D), lambda bh, qi: (bh // group, 0, 0)),
            pl.BlockSpec((1, S, D), lambda bh, qi: (bh // group, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, qc, D), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        interpret=interpret,
    )(q, k, v)


def flash_attention(q, k, v, *, causal=True, window=0, interpret=False,
                    qc=512, kc=512):
    """Model-layout wrapper: q [B,T,H,D], k/v [B,S,K,D] -> [B,T,H,D]."""
    B, T, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, S, D)
    of = flash_attention_pallas(qf, kf, vf, group=G, causal=causal,
                                window=window, qc=qc, kc=kc,
                                interpret=interpret)
    return of.reshape(B, H, T, D).transpose(0, 2, 1, 3)
