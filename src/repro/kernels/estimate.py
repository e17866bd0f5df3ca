"""Pallas TPU kernels: fused field-pair estimators, one per geometry
(the third, key match, is :mod:`repro.kernels.sample_estimate`).

``estimate_fields_pallas`` -- slot-aligned collisions (ICWS/DMH,
Algorithm 5, line 3).  For every (query, corpus row) pair it computes

  * the collision count  ``sum_t 1[fp_q == fp_c]``
  * the importance sum   ``sum_t 1[...] * vq*vc / min(vq^2, vc^2)``

Query and corpus sketches are stacked per *field* (``[F, Q, m]`` /
``[C, P, m]``) and paired by a static list of (query-field, corpus-field)
pairs, so all six §1.3 field-pair estimates of a dataset-search batch run
as one launch; a packed store's bf16 value plane runs through it too.  A
single field pair (``qmap = cmap = (0,)``) is the plain many-vs-many
estimate, and one query (Q = 1) or one row (P = 1) its one-vs-many and
pairwise forms.  It is laid out for the TPU's [8, 128] vregs: queries on
sublanes, corpus rows on lanes, the m slots walked one by one, so every
(query, row) sum is an elementwise accumulation with no lane or sublane
reduction.  The grid ``(Q/bq, P/bp, G, m/bm)`` puts the pairs that share a
corpus field next to each other, so a corpus tile is read from HBM once per
call and serves all its pairs while it is resident.  On the first pass over
P each query slot is broadcast along the lanes into VMEM and stays there;
each corpus tile is transposed once (slots on sublanes, rows on lanes), its
values decoded and ``1/v^2`` taken.  The importance term is
``vq * vc * max(1/vq^2, 1/vc^2)``: the ratio above up to rounding, with no
divide in the inner loop.

``linear_estimate_fields_pallas`` -- MXU dots (CS/JL): per-rep dots of the
stacked tables, with the same pair folding.  Neither kernel
materializes a [Q, P, m] intermediate in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Pad sentinels -- the single definition of the padding convention the
# estimate kernels and the corpus store's inert rows rely on:
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


# The fields kernel's vector layout.  A vreg is [8 sublanes, 128 lanes]:
# queries ride the sublanes, corpus rows the lanes, and the m sketch slots
# are walked one at a time, so each (query, row) output element is a plain
# elementwise running sum over the slots -- no lane or sublane reduction.
_LANES = 128
_SUBLANES = 8
# Slots per inner-loop iteration (unrolled): enough independent vreg work
# to keep the four VALU slots of a v5e bundle busy.
_SLOT_UNROLL = 16
# The collision guard ``fq >= 0`` folded into the data: every negative
# corpus fingerprint (pad -2, empty slot -1) is rewritten to _CORPUS_NEG and
# every negative query fingerprint to _QUERY_NEG before the slot loop.  The
# two never equal each other or a live (>= 0) fingerprint, so a plain
# equality test is exactly ``(fq == fc) & (fq >= 0)``.  The pad sentinels
# alone would not do: an empty query slot and an empty corpus slot both
# hold -1.
_CORPUS_NEG = -2 ** 31
_QUERY_NEG = -2 ** 31 + 1
# VMEM the compiler may use beyond the blocks and scratch the launch sizes.
_VMEM_HEADROOM = 8 * 2 ** 20


def _recip_sq(v):
    """``1 / v^2``, 0 where ``v`` is 0: with it the importance term
    ``vq*vc / min(vq^2, vc^2)`` is ``vq * vc * max(rq, rc)``, both in f32."""
    return jnp.where(v == 0, 0.0, 1.0 / (v * v))


def _fields_kernel(fq_ref, vq_ref, fc_ref, vc_ref, cnt_ref, sw_ref,
                   fct_ref, vct_ref, rct_ref, qf_ref, qv_ref, qr_ref, *,
                   qsel, first, m):
    bq, bm = fq_ref.shape[1:]
    lanes = fc_ref.shape[1] // _LANES
    p, g, mi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    f = qsel(g)
    t0 = mi * bm

    @pl.when(p == 0)
    def _broadcast_queries():
        # on the first pass over P, every slot of the query block becomes a
        # [bq, 128] tile holding each query's fingerprint, value and 1/v^2
        # along all the lanes; the tiles stay in VMEM for the rest of P
        def chunk(c, carry):
            c0 = pl.multiple_of(c * _LANES, _LANES)
            fq = fq_ref[0, :, pl.ds(c0, _LANES)]
            vq = vq_ref[0, :, pl.ds(c0, _LANES)]
            for dst, x in ((qf_ref, jnp.where(fq >= 0, fq, _QUERY_NEG)),
                           (qv_ref, vq), (qr_ref, _recip_sq(vq))):
                dst[f, pl.ds(t0 + c0, _LANES)] = jnp.broadcast_to(
                    x.T[:, :, None], (_LANES, bq, _LANES))
            return carry

        jax.lax.fori_loop(0, bm // _LANES, chunk, 0)

    # a corpus tile arrives [bp, bm], rows on sublanes; when its field's
    # first pair comes up it is transposed to [bm, 128] per 128 rows, the
    # packed bf16 value plane decoded and 1/v^2 taken on the way
    @pl.when(first(g) == 1)
    def _transpose_corpus():
        for j in range(lanes):
            rows = pl.ds(j * _LANES, _LANES)
            fc = fc_ref[0, rows, :]
            fct_ref[j] = jnp.where(fc >= 0, fc, _CORPUS_NEG).T
            vc = vc_ref[0, rows, :].astype(jnp.float32).T
            vct_ref[j] = vc
            rct_ref[j] = _recip_sq(vc)

    @pl.when(mi == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        sw_ref[...] = jnp.zeros_like(sw_ref)

    tile = (lanes, bq, _LANES)
    cols = [pl.ds(j * _LANES, _LANES) for j in range(lanes)]

    def slots(i, acc):
        cnt, sw = acc
        for u in range(_SLOT_UNROLL):
            t = i * _SLOT_UNROLL + u
            fq, vq, rq = (jnp.broadcast_to(r[f, t0 + t], tile)
                          for r in (qf_ref, qv_ref, qr_ref))
            # slot t of each corpus row, repeated down the sublanes (a
            # sublane-broadcast load on the chip)
            fc, vc, rc = (jnp.broadcast_to(r[:, pl.ds(t, 1), :], tile)
                          for r in (fct_ref, vct_ref, rct_ref))
            hit = fc == fq
            cnt = cnt + jnp.where(hit, 1.0, 0.0)
            sw = sw + jnp.where(hit, vc * vq * jnp.maximum(rc, rq), 0.0)
        return cnt, sw

    # the block's live slots only (m may end inside it); the slots of a
    # last partial iteration are padding, which never collides
    live = jnp.minimum(bm, m - t0)
    acc = tuple(jnp.stack([r[0, :, c] for c in cols]) for r in (cnt_ref, sw_ref))
    cnt, sw = jax.lax.fori_loop(0, pl.cdiv(live, _SLOT_UNROLL), slots, acc)
    for j, c in enumerate(cols):
        cnt_ref[0, :, c] = cnt[j]
        sw_ref[0, :, c] = sw[j]


@functools.partial(jax.jit, static_argnames=("qmap", "cmap", "bq", "bp", "bm",
                                             "interpret"))
def estimate_fields_pallas(fq, vq, fpc, vc, *, qmap, cmap, bq: int = 16,
                           bp: int = 512, bm: int = 256,
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
      bq/bp/bm: at most ``bq`` queries (a multiple of 8) per block; ``bp``
        corpus rows (a multiple of 128) per block; a sketch no wider than
        ``bm`` is one slot block, a wider one goes in 128-slot blocks.
    Returns (n_collide [G, Q, P], s_weight [G, Q, P]) with G = len(qmap).

    Grid ``(Q/bq, P/bp, G, m/bm)``, the pairs ordered by corpus field, so
    each corpus tile is read from HBM once and serves every pair of its
    field while it is resident; the query blocks stay resident too.  In a
    step queries lie on sublanes and corpus rows on lanes.  The first pass
    over P broadcasts each query slot along the lanes into VMEM (1.5 KiB
    per field, slot and query of the block: 18 MiB at F = 3, m = 256 and
    16 queries, which a v5e's 128 MiB holds); each corpus tile is
    transposed once, so a slot of 128 rows loads as one sublane-broadcast
    row; and every (query, row) pair accumulates its collisions and
    importance terms elementwise, slot after slot.  So each output element
    sums its own m terms in slot order, whatever Q, P, the blocks, the
    shard or the tenant slice: batched, sharded, tenant and packed
    launches are bitwise equal to their plain forms.
    """
    F, Q, m = fq.shape
    C, P, _ = fpc.shape
    qmap, cmap = _check_maps(qmap, cmap, F, C)
    G = len(qmap)
    bq = min(bq, _SUBLANES * pl.cdiv(Q, _SUBLANES))
    m_lanes = _LANES * pl.cdiv(m, _LANES)
    bm = m_lanes if m_lanes <= bm else _LANES
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

    # pairs grouped by corpus field: a field's tile stays put across them,
    # transposed by the first; a sketch of several slot blocks brings a new
    # tile every step
    n_m = mp // bm
    order = sorted(range(G), key=lambda g: cmap[g])
    gsel = _lut(order)
    qsel = _lut(tuple(qmap[g] for g in order))
    csel = _lut(tuple(cmap[g] for g in order))
    first = _lut(tuple(int(n_m > 1 or i == 0
                           or cmap[order[i]] != cmap[order[i - 1]])
                       for i in range(G)))
    last_q = (qmap[order[-1]], n_m - 1)

    def q_index(qb, p, g, mi):
        # past the first pass over P the query block never changes, so it
        # is not fetched again
        return (jnp.where(p == 0, qsel(g), last_q[0]), qb,
                jnp.where(p == 0, mi, last_q[1]))

    scratch = [pltpu.VMEM((bp // _LANES, bm, _LANES), jnp.int32),
               pltpu.VMEM((bp // _LANES, bm, _LANES), jnp.float32),
               pltpu.VMEM((bp // _LANES, bm, _LANES), jnp.float32),
               pltpu.VMEM((F, mp, bq, _LANES), jnp.int32),
               pltpu.VMEM((F, mp, bq, _LANES), jnp.float32),
               pltpu.VMEM((F, mp, bq, _LANES), jnp.float32)]
    # scratch, plus every block twice (double-buffered), 4 bytes an element
    vmem = 4 * (3 * bp * bm + 3 * F * mp * bq * _LANES
                + 2 * (2 * bq * bm + 2 * bp * bm + 2 * bq * bp))
    kernel = functools.partial(_fields_kernel, qsel=qsel, first=first, m=m)
    cnt, sw = pl.pallas_call(
        kernel,
        grid=(Qp // bq, Pp // bp, G, n_m),
        in_specs=[
            pl.BlockSpec((1, bq, bm), q_index),
            pl.BlockSpec((1, bq, bm), q_index),
            pl.BlockSpec((1, bp, bm), lambda qb, p, g, mi: (csel(g), p, mi)),
            pl.BlockSpec((1, bp, bm), lambda qb, p, g, mi: (csel(g), p, mi)),
        ],
        out_specs=[pl.BlockSpec((1, bq, bp),
                                lambda qb, p, g, mi: (gsel(g), qb, p))] * 2,
        out_shape=[jax.ShapeDtypeStruct((G, Qp, Pp), jnp.float32)] * 2,
        scratch_shapes=scratch,
        # sequential grid: the query broadcast of the first pass over P
        # and each corpus tile's transpose are reused by later steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4,
            vmem_limit_bytes=vmem + _VMEM_HEADROOM),
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
