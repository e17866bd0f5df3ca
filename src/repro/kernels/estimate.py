"""Pallas TPU kernels: fused sketch-pair estimator partials (Algorithm 5, line 3).

For P sketch pairs with m samples each, computes per pair:
  * the collision count  ``sum_t 1[fp_a == fp_b]``
  * the importance sum   ``sum_t 1[...] * va*vb / min(va^2, vb^2)``

Four variants share the kernel math:

  * ``estimate_partials_pallas``          -- pairwise: A and B are both [P, m].
  * ``estimate_one_vs_many_pallas``       -- one query sketch [1, m] against a
    corpus [P, m].  The query block is *broadcast* across the P grid dimension
    via its BlockSpec index map (every grid step re-reads block (0, mi)), so
    the caller never tiles the query into a [P, m] copy.
  * ``estimate_many_vs_many_pallas``      -- Q query sketches against a corpus
    [P, m] in ONE launch, grid ``(Q/BQ, P/BP, m/BM)``.  Each query block is
    re-read across the P grid dimension exactly the way the one-vs-many
    variant broadcasts its single row; collisions are formed blockwise as
    ``[BQ, BP, BM]`` in VMEM and reduced immediately -- no ``[Q, P, m]``
    tensor is ever materialized.
  * ``estimate_fields_pallas``            -- the fused multi-field form of the
    above: query/corpus sketches arrive stacked per *field* (``[F, Q, m]`` /
    ``[C, P, m]``) and a static list of (query-field, corpus-field) pairs is
    folded into the leading grid dimension, so e.g. all six §1.3 field-pair
    estimates of a dataset-search batch run as a single kernel launch.  The
    served kernel: a packed store's bf16 value plane runs through it too.

``linear_estimate_fields_pallas`` serves the linear families (CS/JL): per-rep
MXU dots of the stacked tables, the same pair folding.

Grids keep the m dimension innermost and accumulate into per-(row[, col])
output blocks.  Pure VPU elementwise + reduction; one pass over the sketches,
no intermediate [P, m] / [Q, P, m] materialization in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# Pad sentinels -- the single definition of the padding convention every
# estimate variant (and the corpus store / sharded wrappers) relies on:
# query padding (-1, also the empty-sketch fingerprint) and corpus padding
# (-2) never equal each other or a live fingerprint (>= 0), and the kernel
# guard ``fq >= 0`` keeps both out of the estimate.
QUERY_PAD_FP = -1
CORPUS_PAD_FP = -2


def _lut(table):
    """Static python-int lookup expressed as select arithmetic: index maps
    may not capture traced constants, only combine grid indices with
    python scalars."""
    def sel(g):
        idx = table[0]
        for i, v in enumerate(table[1:], start=1):
            idx = jnp.where(g == i, v, idx)
        return idx
    return sel


def _check_maps(qmap, cmap, F, C):
    qmap = tuple(int(i) for i in qmap)
    cmap = tuple(int(i) for i in cmap)
    if len(qmap) != len(cmap):
        raise ValueError("qmap/cmap length mismatch")
    if not qmap:
        raise ValueError("qmap/cmap must name at least one field pair")
    if min(qmap) < 0 or max(qmap) >= F or min(cmap) < 0 or max(cmap) >= C:
        raise ValueError("field map index out of range")
    return qmap, cmap


def _est_kernel(fpa_ref, va_ref, fpb_ref, vb_ref, cnt_ref, sw_ref):
    m_idx = pl.program_id(1)

    fpa, fpb = fpa_ref[:, :], fpb_ref[:, :]
    va, vb = va_ref[:, :], vb_ref[:, :]
    collide = (fpa == fpb) & (fpa >= 0)
    q = jnp.minimum(va * va, vb * vb)
    safe_q = jnp.where(collide & (q > 0), q, 1.0)
    term = jnp.where(collide, va * vb / safe_q, 0.0)
    cnt = collide.astype(jnp.float32).sum(axis=1)
    sw = term.sum(axis=1)

    @pl.when(m_idx == 0)
    def _init():
        cnt_ref[:] = cnt
        sw_ref[:] = sw

    @pl.when(m_idx != 0)
    def _acc():
        cnt_ref[:] = cnt_ref[:] + cnt
        sw_ref[:] = sw_ref[:] + sw


@functools.partial(jax.jit, static_argnames=("bp", "bm", "interpret"))
def estimate_partials_pallas(fpa, va, fpb, vb, *, bp: int = 8, bm: int = 128,
                             interpret: bool = False):
    """Matches :func:`repro.kernels.ref.estimate_partials_ref`."""
    P, m = fpa.shape
    p_pad = (-P) % bp
    m_pad = (-m) % bm
    if p_pad or m_pad:
        fpa = jnp.pad(fpa, ((0, p_pad), (0, m_pad)), constant_values=QUERY_PAD_FP)
        fpb = jnp.pad(fpb, ((0, p_pad), (0, m_pad)), constant_values=CORPUS_PAD_FP)
        va = jnp.pad(va, ((0, p_pad), (0, m_pad)))
        vb = jnp.pad(vb, ((0, p_pad), (0, m_pad)))
    Pp, mp = fpa.shape
    grid = (Pp // bp, mp // bm)
    cnt, sw = pl.pallas_call(
        _est_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bp, bm), lambda p, mi: (p, mi))] * 4,
        out_specs=[pl.BlockSpec((bp,), lambda p, mi: (p,))] * 2,
        out_shape=[jax.ShapeDtypeStruct((Pp,), jnp.float32)] * 2,
        interpret=interpret,
    )(fpa.astype(jnp.int32), va.astype(jnp.float32),
      fpb.astype(jnp.int32), vb.astype(jnp.float32))
    return cnt[:P], sw[:P]


@functools.partial(jax.jit, static_argnames=("bp", "bm", "interpret"))
def estimate_one_vs_many_pallas(fq, vq, fpc, vc, *, bp: int = 64, bm: int = 128,
                                interpret: bool = False):
    """One query sketch against a P-row corpus; matches
    :func:`repro.kernels.ref.estimate_one_vs_many_ref`.

    Args: fq/vq [1, m] (or [m]) query fingerprints/values; fpc/vc [P, m]
    corpus.  Returns (n_collide [P], s_weight [P]).  The query block is
    broadcast by its index map -- no [P, m] tiling of the query ever exists.
    """
    fq = fq.reshape(1, -1)
    vq = vq.reshape(1, -1)
    P, m = fpc.shape
    p_pad = (-P) % bp
    m_pad = (-m) % bm
    if m_pad:
        # pad fingerprints to *different* sentinels so padding never collides
        fq = jnp.pad(fq, ((0, 0), (0, m_pad)), constant_values=QUERY_PAD_FP)
        vq = jnp.pad(vq, ((0, 0), (0, m_pad)))
    if p_pad or m_pad:
        fpc = jnp.pad(fpc, ((0, p_pad), (0, m_pad)), constant_values=CORPUS_PAD_FP)
        vc = jnp.pad(vc, ((0, p_pad), (0, m_pad)))
    Pp, mp = fpc.shape
    grid = (Pp // bp, mp // bm)
    cnt, sw = pl.pallas_call(
        _est_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm), lambda p, mi: (0, mi)),   # query: broadcast
            pl.BlockSpec((1, bm), lambda p, mi: (0, mi)),
            pl.BlockSpec((bp, bm), lambda p, mi: (p, mi)),  # corpus: tiled
            pl.BlockSpec((bp, bm), lambda p, mi: (p, mi)),
        ],
        out_specs=[pl.BlockSpec((bp,), lambda p, mi: (p,))] * 2,
        out_shape=[jax.ShapeDtypeStruct((Pp,), jnp.float32)] * 2,
        interpret=interpret,
    )(fq.astype(jnp.int32), vq.astype(jnp.float32),
      fpc.astype(jnp.int32), vc.astype(jnp.float32))
    return cnt[:P], sw[:P]


def _mvm_body(fq, vq, fc, vc):
    """Blockwise many-vs-many partials: [BQ, BM] x [BP, BM] -> [BQ, BP].

    The [BQ, BP, BM] collision tensor lives only in VMEM for this block.
    """
    fqb, fcb = fq[:, None, :], fc[None, :, :]
    vqb, vcb = vq[:, None, :], vc[None, :, :]
    collide = (fqb == fcb) & (fqb >= 0)
    q = jnp.minimum(vqb * vqb, vcb * vcb)
    safe_q = jnp.where(collide & (q > 0), q, 1.0)
    term = jnp.where(collide, vqb * vcb / safe_q, 0.0)
    return collide.astype(jnp.float32).sum(axis=2), term.sum(axis=2)


def _mvm_kernel(fq_ref, vq_ref, fc_ref, vc_ref, cnt_ref, sw_ref):
    m_idx = pl.program_id(2)
    cnt, sw = _mvm_body(fq_ref[:, :], vq_ref[:, :], fc_ref[:, :], vc_ref[:, :])

    @pl.when(m_idx == 0)
    def _init():
        cnt_ref[:, :] = cnt
        sw_ref[:, :] = sw

    @pl.when(m_idx != 0)
    def _acc():
        cnt_ref[:, :] = cnt_ref[:, :] + cnt
        sw_ref[:, :] = sw_ref[:, :] + sw


@functools.partial(jax.jit,
                   static_argnames=("bq", "bp", "bm", "interpret"))
def estimate_many_vs_many_pallas(fq, vq, fpc, vc, *, bq: int = 8,
                                 bp: int = 128, bm: int = 128,
                                 interpret: bool = False):
    """Q query sketches against a P-row corpus in one launch; matches
    :func:`repro.kernels.ref.estimate_many_vs_many_ref`.

    Args: fq/vq [Q, m] query fingerprints/values; fpc/vc [P, m] corpus.
    Returns (n_collide [Q, P], s_weight [Q, P]).  Grid (Q/bq, P/bp, m/bm),
    m innermost; the query block's index map ignores the P grid index, so
    every query block is re-read (broadcast) across the corpus dimension and
    no [Q, P, m] intermediate ever exists outside a [bq, bp, bm] VMEM tile.
    """
    Q, m = fq.shape
    P, _ = fpc.shape
    q_pad = (-Q) % bq
    p_pad = (-P) % bp
    m_pad = (-m) % bm
    if q_pad or m_pad:
        # distinct pad sentinels: query padding (-1) never collides with
        # corpus padding (-2), and fq >= 0 guards both out of the estimate
        fq = jnp.pad(fq, ((0, q_pad), (0, m_pad)), constant_values=QUERY_PAD_FP)
        vq = jnp.pad(vq, ((0, q_pad), (0, m_pad)))
    if p_pad or m_pad:
        fpc = jnp.pad(fpc, ((0, p_pad), (0, m_pad)), constant_values=CORPUS_PAD_FP)
        vc = jnp.pad(vc, ((0, p_pad), (0, m_pad)))
    Qp, mp = fq.shape
    Pp = fpc.shape[0]
    grid = (Qp // bq, Pp // bp, mp // bm)
    cnt, sw = pl.pallas_call(
        _mvm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bm), lambda q, p, mi: (q, mi)),   # re-read over p
            pl.BlockSpec((bq, bm), lambda q, p, mi: (q, mi)),
            pl.BlockSpec((bp, bm), lambda q, p, mi: (p, mi)),
            pl.BlockSpec((bp, bm), lambda q, p, mi: (p, mi)),
        ],
        out_specs=[pl.BlockSpec((bq, bp), lambda q, p, mi: (q, p))] * 2,
        out_shape=[jax.ShapeDtypeStruct((Qp, Pp), jnp.float32)] * 2,
        interpret=interpret,
    )(fq.astype(jnp.int32), vq.astype(jnp.float32),
      fpc.astype(jnp.int32), vc.astype(jnp.float32))
    return cnt[:Q, :P], sw[:Q, :P]


def _fields_kernel(fq_ref, vq_ref, fc_ref, vc_ref, cnt_ref, sw_ref):
    m_idx = pl.program_id(3)
    # a packed store's bf16 value tile decodes here, in VMEM (exact), to
    # the same tile the unpacked path reads -- one reduction for both
    cnt, sw = _mvm_body(fq_ref[0, :, :], vq_ref[0, :, :], fc_ref[0, :, :],
                        vc_ref[0, :, :].astype(jnp.float32))

    @pl.when(m_idx == 0)
    def _init():
        cnt_ref[0, :, :] = cnt
        sw_ref[0, :, :] = sw

    @pl.when(m_idx != 0)
    def _acc():
        cnt_ref[0, :, :] = cnt_ref[0, :, :] + cnt
        sw_ref[0, :, :] = sw_ref[0, :, :] + sw


@functools.partial(jax.jit, static_argnames=("qmap", "cmap", "bq", "bp", "bm",
                                             "interpret"))
def estimate_fields_pallas(fq, vq, fpc, vc, *, qmap, cmap, bq: int = 8,
                           bp: int = 128, bm: int = 128,
                           interpret: bool = False):
    """Fused multi-field many-vs-many partials in ONE kernel launch; matches
    :func:`repro.kernels.ref.estimate_fields_ref`.

    Args:
      fq/vq: [F, Q, m] per-field query sketches.
      fpc/vc: [C, P, m] per-field corpus sketches; ``vc`` is f32, or the
        bf16 value plane of a packed store (:mod:`repro.kernels.packed`),
        decoded tile by tile in VMEM -- the f32 plane never exists in HBM.
      qmap/cmap: static same-length tuples of field indices; estimate ``g``
        pairs query field ``qmap[g]`` with corpus field ``cmap[g]`` (§1.3
        uses six such pairs over F = C = 3 fields).
    Returns (n_collide [G, Q, P], s_weight [G, Q, P]) with G = len(qmap).

    The pair list is folded into the leading grid dimension: the query /
    corpus BlockSpec index maps gather the right field via a static lookup
    table, so no per-pair [Q, m] / [P, m] copies are ever stacked in HBM.
    """
    F, Q, m = fq.shape
    C, P, _ = fpc.shape
    qmap, cmap = _check_maps(qmap, cmap, F, C)
    G = len(qmap)
    q_pad = (-Q) % bq
    p_pad = (-P) % bp
    m_pad = (-m) % bm
    if q_pad or m_pad:
        fq = jnp.pad(fq, ((0, 0), (0, q_pad), (0, m_pad)), constant_values=QUERY_PAD_FP)
        vq = jnp.pad(vq, ((0, 0), (0, q_pad), (0, m_pad)))
    if p_pad or m_pad:
        fpc = jnp.pad(fpc, ((0, 0), (0, p_pad), (0, m_pad)),
                      constant_values=CORPUS_PAD_FP)
        vc = jnp.pad(vc, ((0, 0), (0, p_pad), (0, m_pad)))
    Qp, mp = fq.shape[1:]
    Pp = fpc.shape[1]

    qsel, csel = _lut(qmap), _lut(cmap)
    grid = (G, Qp // bq, Pp // bp, mp // bm)
    cnt, sw = pl.pallas_call(
        _fields_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, bm), lambda g, q, p, mi: (qsel(g), q, mi)),
            pl.BlockSpec((1, bq, bm), lambda g, q, p, mi: (qsel(g), q, mi)),
            pl.BlockSpec((1, bp, bm), lambda g, q, p, mi: (csel(g), p, mi)),
            pl.BlockSpec((1, bp, bm), lambda g, q, p, mi: (csel(g), p, mi)),
        ],
        out_specs=[pl.BlockSpec((1, bq, bp),
                                lambda g, q, p, mi: (g, q, p))] * 2,
        out_shape=[jax.ShapeDtypeStruct((G, Qp, Pp), jnp.float32)] * 2,
        interpret=interpret,
    )(fq.astype(jnp.int32), vq.astype(jnp.float32),
      fpc.astype(jnp.int32), vc)
    return cnt[:, :Q, :P], sw[:, :Q, :P]


# ---------------------------------------------------------------------------
# Linear-family estimation: per-rep sketch dots as MXU matmuls
# ---------------------------------------------------------------------------
def _rep_dots(a, b, out_ref):
    """``[BQ, R, BW] x [BP, R, BW] -> out_ref[0, r] += [BQ, BP]`` per rep,
    at full f32 precision on the MXU."""
    for r in range(a.shape[1]):
        tile = jax.lax.dot_general(
            a[:, r, :], b[:, r, :], (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)               # [BQ, BP]
        out_ref[0, r, :, :] += tile


def _linear_fields_kernel(tq_ref, tc_ref, out_ref):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # a packed store's bf16 tables decode here, in VMEM (exact)
    _rep_dots(tq_ref[0], tc_ref[0].astype(jnp.float32), out_ref)


@functools.partial(jax.jit, static_argnames=("qmap", "cmap", "bq", "bp", "bw",
                                             "interpret"))
def linear_estimate_fields_pallas(tq, tc, *, qmap, cmap, bq: int = 8,
                                  bp: int = 128, bw: int = 512,
                                  interpret: bool = False):
    """Fused multi-field per-rep linear-sketch dots in ONE kernel launch;
    matches :func:`repro.kernels.ref.linear_estimate_fields_ref`.

    Args:
      tq: [F, Q, R, W] per-field query tables (JL: R = 1, W = m).
      tc: [C, P, R, W] per-field corpus tables, f32 or the bf16 tables of a
        packed store (decoded tile by tile in VMEM).
      qmap/cmap: static same-length tuples of field indices, exactly as
        :func:`estimate_fields_pallas`.
    Returns [G, R, Q, P] f32 per-rep inner products: each ``[BQ, BW] @
    [BW, BP]`` tile is MXU work (full f32 precision), accumulated over the
    (innermost) W grid dimension; all R reps of a tile run in one grid step
    (R is the full second-minor dim of the blocks).  The pair list folds
    into the leading grid dimension the same way the ICWS fields kernel
    folds it, so all G * R dot matrices of a dataset-search batch run as a
    single launch.  The median-of-reps (CS) / squeeze (JL) epilogue belongs
    to the caller.  A table no wider than ``bw`` is one block (a block that
    spans the full array dim is legal on a TPU at any width, and needs no
    corpus padding); wider tables tile by ``bw``, a lane multiple.

    Zero padding is inert everywhere: padded W lanes add 0 to every dot,
    and padded Q/P rows only produce extra output rows that are sliced off
    -- per-(q, p) results are bitwise independent of Q, P, and row padding.
    """
    F, Q, R, W = tq.shape
    C, P, Rc, Wc = tc.shape
    qmap, cmap = _check_maps(qmap, cmap, F, C)
    if (R, W) != (Rc, Wc):
        raise ValueError(f"query tables {(R, W)} do not match corpus "
                         f"tables {(Rc, Wc)}")
    G = len(qmap)
    if W <= bw:
        bw = W
    q_pad = (-Q) % bq
    p_pad = (-P) % bp
    w_pad = (-W) % bw
    if q_pad or w_pad:
        tq = jnp.pad(tq, ((0, 0), (0, q_pad), (0, 0), (0, w_pad)))
    if p_pad or w_pad:
        tc = jnp.pad(tc, ((0, 0), (0, p_pad), (0, 0), (0, w_pad)))
    Qp, Pp, Wp = Q + q_pad, P + p_pad, W + w_pad
    qsel, csel = _lut(qmap), _lut(cmap)
    out = pl.pallas_call(
        _linear_fields_kernel,
        grid=(G, Qp // bq, Pp // bp, Wp // bw),
        in_specs=[
            pl.BlockSpec((1, bq, R, bw),
                         lambda g, q, p, wi: (qsel(g), q, 0, wi)),
            pl.BlockSpec((1, bp, R, bw),
                         lambda g, q, p, wi: (csel(g), p, 0, wi)),
        ],
        out_specs=pl.BlockSpec((1, R, bq, bp),
                               lambda g, q, p, wi: (g, 0, q, p)),
        out_shape=jax.ShapeDtypeStruct((G, R, Qp, Pp), jnp.float32),
        interpret=interpret,
    )(tq.astype(jnp.float32), tc)
    return out[:, :, :Q, :P]
