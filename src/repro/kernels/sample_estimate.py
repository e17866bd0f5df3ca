"""Pallas TPU kernel: key-match estimation for sampling sketches (TS/PS).

The sampling families (:mod:`repro.core.sampling`) store fixed-slot rows

    ``(key [m] i32, val [m] f32, tau [] f32)``

where the keys of a row are an importance *sample* of the vector's support.
Unlike ICWS rows, slots are NOT aligned: query slot t and corpus slot u
refer to the same coordinate iff their keys are equal, wherever they sit.
The estimate for a (query, corpus-row) pair is therefore a full key-equality
contraction over the ``m x m`` slot pairs,

    ``est[q, p] = sum_{t,u} 1[kq[q,t] == kc[p,u]] * vq[q,t] * vc[p,u]
                            / min(pq[q,t], pc[p,u])``

with inverse-inclusion-probability weights ``p = min(1, m * v^2 / tau)``
(``tau <= 0`` means probability 1; see :func:`sample_inclusion_probs`).
This is a third estimator geometry for the kernel layer: not slot-aligned
collision counting (ICWS), not dense MXU dots (CS/JL), but an unaligned
sparse join, an O(m^2) equality contraction per (query, row) pair.

``sample_estimate_fields_pallas`` is the fused multi-field form, mirroring
:func:`repro.kernels.estimate.estimate_fields_pallas`: per-field stacks
``[F, Q, m]`` / ``[C, P, m]`` plus static qmap/cmap field-pair tuples folded
into the leading grid dimension, so all §1.3 field-pair estimates of a
dataset-search batch run as ONE launch.  The grid is ``(G, P/bp, Q/bq)``,
queries innermost so a corpus tile stays in VMEM across the query blocks.
A step transposes its ``[bp, m]`` corpus tile so slots lie on sublanes and
rows on lanes, then walks the ``bq`` queries and their slots: each slot's
key is compared against the whole ``[m, bp]`` tile and the matches are
reduced over the corpus slots, one ``[1, bp]`` row of sums per slot (VPU
work; VMEM holds a few ``[m, bp]`` f32 tiles, 192 KiB each at m = 384,
bp = 128).  The packed store's bf16 value plane runs through the same
kernel.

Padding reuses the single estimate-kernel sentinel convention
(:mod:`repro.kernels.estimate`): live keys are 31-bit non-negative, query
padding is -1 (also the empty-slot fill of ingested rows), corpus padding
and inert spare store rows are -2, and the ``kq >= 0`` guard keeps all of
them out of the estimate.  Probability 0 marks empty slots (value 0), so
spare rows (zero values, zero tau) estimate to exactly 0.0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .estimate import CORPUS_PAD_FP, QUERY_PAD_FP, _check_maps, _lut

# Sampling rows reuse the estimate kernels' pad convention: empty / padded
# query slots hold -1, corpus padding and spare store rows hold -2.
SAMPLE_QUERY_PAD_KEY = QUERY_PAD_FP
SAMPLE_CORPUS_PAD_KEY = CORPUS_PAD_FP


def _inclusion_probs(v: jnp.ndarray, t: jnp.ndarray, m: int) -> jnp.ndarray:
    """Elementwise inclusion-probability core shared by the query-side
    prologue and the kernel's in-VMEM corpus recompute -- a single
    definition so the two paths are bitwise identical by construction.
    ``v`` f32 values, ``t`` f32 taus broadcastable against ``v``, ``m`` the
    static slot count of the sketch scheme (NOT a padded tile width).
    """
    num = jnp.float32(m) * v * v
    p = jnp.where(t > 0, jnp.minimum(1.0, num / jnp.where(t > 0, t, 1.0)),
                  1.0)
    return jnp.where(v != 0, p, 0.0)


def sample_inclusion_probs(vals: jnp.ndarray, tau: jnp.ndarray) -> jnp.ndarray:
    """Per-slot inclusion probabilities from the stored sample layout.

    Args: vals ``[..., m]`` f32 sampled values (0 marks an empty slot);
    tau ``[...]`` f32 probability scales.  Returns ``[..., m]`` f32
    ``min(1, m * v^2 / tau)`` with ``tau <= 0`` meaning probability 1 and
    empty slots pinned to probability 0 (the kernel's live-slot guard).
    The f64 host twin is :func:`repro.core.sampling.sample_probs`.
    """
    return _inclusion_probs(vals.astype(jnp.float32),
                            tau.astype(jnp.float32)[..., None],
                            vals.shape[-1])


def _sample_fields_kernel(kq_ref, vq_ref, aq_ref, kc_ref, vc_ref, tc_ref,
                          out_ref):
    s_total = kc_ref.shape[2]
    n_q = kq_ref.shape[3]
    # corpus tile with slots on sublanes and rows on lanes: [S, BP].  The
    # value tile decodes in VMEM when it arrives packed (bf16 -> f32,
    # exact) and the inclusion probabilities are recomputed here from the
    # per-row tau, so no probability plane ever exists in HBM.
    kc = kc_ref[0].T
    vc = vc_ref[0].astype(jnp.float32).T
    ac = _inclusion_probs(vc, tc_ref[0], s_total)
    bp = kc.shape[1]

    for q in range(n_q):
        def slot(t, acc, q=q):
            # one query slot against every (slot, row) of the tile; the
            # `kq >= 0` guard drops every pad sentinel and `p > 0` drops
            # empty slots on either side, so pads never divide or match
            kq, vq, aq = (r[0, 0, t, q] for r in (kq_ref, vq_ref, aq_ref))
            p = jnp.minimum(aq, ac)
            live = (kq == kc) & (kq >= 0) & (p > 0)
            term = jnp.where(live, vq * vc / jnp.where(live, p, 1.0), 0.0)
            return acc + jnp.sum(term, axis=0, keepdims=True)

        out_ref[0, q:q + 1, :] = jax.lax.fori_loop(
            0, kq_ref.shape[2], slot, jnp.zeros((1, bp), jnp.float32))


@functools.partial(jax.jit, static_argnames=("qmap", "cmap", "bp", "bq",
                                             "interpret"))
def sample_estimate_fields_pallas(kq, vq, aq, kc, vc, tc, *, qmap, cmap,
                                  bp: int = 128, bq: int = 8,
                                  interpret: bool = False):
    """Fused multi-field key-match estimates in ONE kernel launch; matches
    :func:`repro.kernels.ref.sample_estimate_fields_ref`.

    Args:
      kq/vq/aq: [F, Q, m] per-field query sample keys / values / inclusion
        probabilities (see :func:`sample_inclusion_probs`).
      kc/vc/tc: [C, P, S] corpus sample keys and values, [C, P] taus, as
        stored.  ``vc`` is f32, or the bf16 plane of a packed store (see
        :mod:`repro.kernels.packed`): the kernel decodes it per tile.
      qmap/cmap: static same-length tuples of field indices, exactly as
        :func:`repro.kernels.estimate.estimate_fields_pallas`.
    Returns [G, Q, P] f32 inner-product estimates (no epilogue: the inverse-
    probability weighting happens inside the contraction).

    Grid ``(G, P/bp, Q/bq)``: each step holds one ``[bp, S]`` corpus tile
    and a block of ``bq`` queries of its field (slots on sublanes, the
    block's queries on lanes; Q is padded with inert queries to a multiple
    of ``bq``).
    Corpus inclusion probabilities ``min(1, S * v^2 / tau)`` come from the
    decoded tile and ``tc`` in VMEM, with the same elementwise expression
    the query prologue uses.  Per-(q, p) results are bitwise independent of
    Q and of the corpus row count: each output element reduces only over
    its own query column and corpus row, in a fixed slot order.
    """
    F, Q, m = kq.shape
    C, P, mc = kc.shape
    qmap, cmap = _check_maps(qmap, cmap, F, C)
    if m != mc or vc.shape != kc.shape:
        raise ValueError(f"query slots {m} do not match corpus slots {mc}")
    G = len(qmap)
    p_pad = (-P) % bp
    if p_pad:
        kc = jnp.pad(kc, ((0, 0), (0, p_pad), (0, 0)),
                     constant_values=SAMPLE_CORPUS_PAD_KEY)
        vc = jnp.pad(vc, ((0, 0), (0, p_pad), (0, 0)))
        tc = jnp.pad(tc, ((0, 0), (0, p_pad)))
    Pp = P + p_pad
    q_pad = (-Q) % bq
    Qp = Q + q_pad

    def q_blocks(x, fill):
        # [F, Q, m] -> [F, Qp/bq, m, bq]: a block's queries on lanes
        x = jnp.pad(x, ((0, 0), (0, q_pad), (0, 0)), constant_values=fill)
        return jnp.swapaxes(x.reshape(F, Qp // bq, bq, m), 2, 3)

    qsel, csel = _lut(qmap), _lut(cmap)
    out = pl.pallas_call(
        _sample_fields_kernel,
        grid=(G, Pp // bp, Qp // bq),
        in_specs=[
            pl.BlockSpec((1, 1, m, bq), lambda g, p, j: (qsel(g), j, 0, 0)),
            pl.BlockSpec((1, 1, m, bq), lambda g, p, j: (qsel(g), j, 0, 0)),
            pl.BlockSpec((1, 1, m, bq), lambda g, p, j: (qsel(g), j, 0, 0)),
            pl.BlockSpec((1, bp, m), lambda g, p, j: (csel(g), p, 0)),
            pl.BlockSpec((1, bp, m), lambda g, p, j: (csel(g), p, 0)),
            pl.BlockSpec((1, 1, bp), lambda g, p, j: (csel(g), 0, p)),
        ],
        out_specs=pl.BlockSpec((1, bq, bp), lambda g, p, j: (g, j, p)),
        out_shape=jax.ShapeDtypeStruct((G, Qp, Pp), jnp.float32),
        interpret=interpret,
    )(q_blocks(kq.astype(jnp.int32), SAMPLE_QUERY_PAD_KEY),
      q_blocks(vq.astype(jnp.float32), 0.0),
      q_blocks(aq.astype(jnp.float32), 0.0),
      kc.astype(jnp.int32), vc, tc.astype(jnp.float32)[:, None, :])
    return out[:, :Q, :P]
