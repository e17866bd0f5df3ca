"""jit'd public wrappers around the Pallas kernels.

Dispatch policy: on TPU backends the compiled kernels run natively; on CPU
they run under ``interpret=True`` -- same kernel body,
executed by the Pallas interpreter -- and every op is validated against the
pure-jnp oracles in :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PSpec

from repro import compat
from repro import obs as _obs
from repro.roofline import autotune

from . import ref
from .countsketch import countsketch_pallas, countsketch_sparse_pallas
from .estimate import estimate_fields_pallas, linear_estimate_fields_pallas
from .dmh_sketch import dmh_sketch_pallas, dmh_sketch_scatter
from .icws_sketch import icws_sketch_pallas
from .jl_sketch import jl_sketch_pallas
from .sample_estimate import (sample_estimate_fields_pallas,
                              sample_inclusion_probs)


def _backend() -> str:
    """The backend every launch below is built for -- the one place the
    dispatch policy asks jax, so a compile test can aim the wrappers at a
    described TPU from a CPU-only process."""
    return jax.default_backend()


def _interpret() -> bool:
    interp = _backend() != "tpu"
    if _obs.enabled():
        _obs.gauge("ops.interpret_mode").set(float(interp))
    return interp


def _tuned(kernel: str, key, clamp):
    """Autotuned block kwargs for one launch ({} -> the kernel's defaults).

    Resolution happens at trace time on concrete shapes (the wrappers are
    jit'd with static field maps), so the cache file is consulted once per
    traced shape, never per call.  Row-dim blocks are clamped to the
    launch's padded row count (:func:`repro.roofline.autotune.resolve`);
    reduction-dim blocks come back exactly as tuned, keyed only by the
    sketch width, which is what keeps every bitwise ranking identity
    (batched/sequential, sharded/single-device, tenant, packed/unpacked)
    intact under tuning.
    """
    blocks = autotune.resolve(kernel, _backend(), key, clamp=clamp)
    if _obs.enabled():
        _obs.counter("ops.autotune_resolved_total", kernel=kernel,
                     source="tuned" if blocks else "default").inc()
    return blocks


@_obs.instrumented("icws_sketch")
def icws_sketch(w, keys, vals, *, m: int, seed: int = 0,
                pack_vals: bool = False):
    """Device ICWS sketch of padded sparse batch.
    [B,N] -> (fp, val, amin, argkey) [B,m].

    Rows are sketched 8 per grid step (the TPU tiling rule's row block; a
    single query's 3 field rows pad to one block) unless a tuned
    ``icws_sketch`` cache entry (keyed by (m, N)) says otherwise.  Results
    are bitwise identical either way.  ``pack_vals=True`` appends the
    ``[B, m]`` bf16 packed value plane as a fifth output (see
    :func:`icws_sketch_pallas`).
    """
    blocks = _tuned("icws_sketch", {"m": m, "N": w.shape[1]},
                    {"br": (w.shape[0], 1)})
    return icws_sketch_pallas(w, keys, vals, m=m, seed=seed,
                              pack_vals=pack_vals, interpret=_interpret(),
                              **blocks)


@_obs.instrumented("dmh_sketch")
def dmh_sketch(w, keys, vals, *, m: int, seed: int = 0,
               pack_vals: bool = False):
    """Device DMH sketch of a padded sparse batch -- same signature and
    ``(fp, val, amin, argkey)`` wire layout as :func:`icws_sketch`, but
    O(nnz + m) per row instead of O(nnz * m): each non-zero is binned once
    and only the per-bin minima are kept (see
    :mod:`repro.kernels.dmh_sketch`).

    The VMEM bin-state width ``bm`` is fixed here to the lane-rounded
    sketch width -- it is a capacity, not a tuning knob, so the autotune
    cache only carries (br, bn); rows go 8 per grid step without an entry.
    Results are bitwise identical across all block choices.

    Without a compiled Pallas backend the kernel's ``[br, bm, bn]``
    bin-equality cross (free across TPU VPU lanes) would be materialized
    by interpret mode, silently re-inflating DMH to the O(nnz * m) cost it
    exists to avoid -- so the interpret branch dispatches to
    :func:`repro.kernels.dmh_sketch.dmh_sketch_scatter`, the scatter-min
    lowering of the same contract (same winners, same wire layout).
    """
    if _interpret():
        return dmh_sketch_scatter(w, keys, vals, m=m, seed=seed,
                                  pack_vals=pack_vals)
    blocks = _tuned("dmh_sketch", {"m": m, "N": w.shape[1]},
                    {"br": (w.shape[0], 1)})
    blocks.pop("bm", None)
    bm = 128 * (-(-max(m, 1) // 128))
    return dmh_sketch_pallas(w, keys, vals, m=m, seed=seed, bm=bm,
                             pack_vals=pack_vals, interpret=False, **blocks)


@_obs.instrumented("countsketch")
def countsketch(x, *, width: int, reps: int = 5, seed: int = 0, offset: int = 0):
    """CountSketch table [reps, width] of a dense vector."""
    return countsketch_pallas(x, width=width, reps=reps, seed=seed,
                              offset=offset, interpret=_interpret())


@_obs.instrumented("countsketch_decode")
def countsketch_decode(table, indices, *, seed: int = 0):
    """Unbiased median-of-reps point query (pure jnp: gather-bound, no kernel)."""
    return ref.countsketch_decode_ref(table, indices, seed)


@_obs.instrumented("countsketch_sparse")
def countsketch_sparse(keys, vals, *, width: int, reps: int = 5,
                       seed: int = 0):
    """Device CountSketch of a padded sparse batch.  [B, N] -> [B, reps, width]."""
    return countsketch_sparse_pallas(keys, vals, width=width, reps=reps,
                                     seed=seed, interpret=_interpret())


@_obs.instrumented("jl_sketch")
def jl_sketch(keys, vals, *, m: int, seed: int = 0):
    """Device JL projection of a padded sparse batch.  [B, N] -> [B, m]."""
    return jl_sketch_pallas(keys, vals, m=m, seed=seed,
                            interpret=_interpret())


@_obs.instrumented("estimate_partials_fields")
def estimate_partials_fields(fq, vq, fpc, vc, *, qmap, cmap):
    """Fused multi-field partial sums: one launch for all field pairs."""
    blocks = _tuned("estimate_fields", {"m": fpc.shape[2]},
                    {"bq": (fq.shape[1], 8), "bp": (fpc.shape[1], 128)})
    return estimate_fields_pallas(fq, vq, fpc, vc, qmap=tuple(qmap),
                                  cmap=tuple(cmap), interpret=_interpret(),
                                  **blocks)


@_obs.instrumented("linear_estimate_fields", packed_arg=1)
@functools.partial(jax.jit, static_argnames=("qmap", "cmap"))
def linear_estimate_fields(tq, tc, *, qmap, cmap):
    """Fused multi-field linear-sketch estimates: all field pairs, ONE launch.

    Args: tq [F, Q, R, W] per-field query tables, tc [C, P, R, W] per-field
    corpus tables (JL: R = 1, W = m); qmap/cmap static length-G field-pair
    maps.  Returns [G, Q, P] f32 estimates: per-rep MXU dot products from
    :func:`linear_estimate_fields_pallas`, then the unbiasing epilogue --
    the median over repetitions (for R = 1 the median IS the single dot, so
    JL and CS share this one wrapper).  Zero rows (empty sketches, spare
    store capacity, padding) estimate to zero with no sentinel machinery.
    """
    blocks = _tuned("linear_estimate_fields", {"W": tq.shape[3]},
                    {"bq": (tq.shape[1], 8), "bp": (tc.shape[1], 128)})
    dots = linear_estimate_fields_pallas(tq, tc, qmap=qmap, cmap=cmap,
                                         interpret=_interpret(), **blocks)
    return jnp.median(dots, axis=1)


@_obs.instrumented("icws_estimate_fields", packed_arg=4)
@functools.partial(jax.jit, static_argnames=("qmap", "cmap"))
def icws_estimate_fields(fq, vq, nq, fpc, vc, nc, *, qmap, cmap):
    """Fused multi-field ICWS estimates: all field pairs in ONE launch.

    Args: fq/vq [F, Q, m] per-field queries, nq [F, Q] norms; fpc/vc
    [C, P, m] per-field corpus (vc f32, or a packed store's bf16 plane),
    nc [C, P] norms; qmap/cmap static length-G
    field-pair maps.  Returns [G, Q, P] f32 estimates -- for §1.3 dataset
    search, the six estimate launches of the sequential path collapse into
    this single call.
    """
    m = fpc.shape[2]
    cnt, sw = estimate_partials_fields(fq, vq, fpc, vc, qmap=qmap, cmap=cmap)
    j_hat = cnt / m
    m_tilde = 2.0 / (1.0 + j_hat)
    nqg = jnp.stack([nq[qf] for qf in qmap])[:, :, None]    # [G, Q, 1]
    ncg = jnp.stack([nc[cf] for cf in cmap])[:, None, :]    # [G, 1, P]
    est = nqg * ncg * (m_tilde / m) * sw
    return jnp.where((nqg == 0) | (ncg == 0), 0.0, est)


@_obs.instrumented("sample_estimate_fields", packed_arg=4)
@functools.partial(jax.jit, static_argnames=("qmap", "cmap"))
def sample_estimate_fields(kq, vq, tq, kc, vc, tc, *, qmap, cmap):
    """Fused multi-field sampling-sketch (TS/PS) estimates, ONE launch.

    Args: kq/vq [F, Q, m] per-field query sample keys/values, tq [F, Q]
    probability scales; kc/vc [C, P, m] / tc [C, P] corpus samples (vc f32
    or a packed store's bf16 plane); qmap/cmap static length-G field-pair
    maps.  Returns [G, Q, P] f32 inverse-inclusion-probability estimates
    from the key-match kernel -- the query probabilities ``min(1, m * v^2 /
    tau)`` are reconstructed here (elementwise prologue), the corpus ones
    inside the kernel, so the stored layout stays (key, val, tau).
    """
    aq = sample_inclusion_probs(vq, tq)
    blocks = _tuned("sample_estimate_fields", {"S": kq.shape[2]},
                    {"bp": (kc.shape[1], 128)})
    return sample_estimate_fields_pallas(kq, vq, aq, kc, vc, tc,
                                         qmap=qmap, cmap=cmap,
                                         interpret=_interpret(), **blocks)


# ---------------------------------------------------------------------------
# sharded query execution: corpus rows spread over a mesh axis
# ---------------------------------------------------------------------------
# Each shard runs the same single-device fields launch on its slice of the
# corpus rows with the queries replicated; because every corpus row's
# estimate is independent of every other row (the kernels reduce only over
# the sample axis, with identical block sizes on any row count), the
# concatenated per-shard results are bitwise identical to the single-device
# launch, for every geometry and for packed buffers alike.

def _pad_corpus_rows(x, pad: int, axis: int, value=0):
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# The shard_map-transformed callables are built once per (launch, maps,
# mesh, axis) and cached: rebuilding the closure per call would change the
# transformed function's identity and defeat jax's tracing cache on the
# serving hot path -- exactly the per-launch overhead the batched engine
# amortizes.

@functools.lru_cache(maxsize=None)
def _corpus_sharded_fn(launch, qmap, cmap, mesh, axis: str):
    def body(q, c):
        return launch(q, c, qmap=qmap, cmap=cmap)

    return compat.shard_map(body, mesh=mesh,
                            in_specs=(PSpec(), PSpec(None, axis)),
                            out_specs=PSpec(None, None, axis))


@_obs.instrumented("estimate_fields_sharded")
def estimate_fields_sharded(launch, q, c, *, qmap, cmap, mesh, axis="data"):
    """Run a single-device fields launch with the corpus rows split over
    mesh axis ``axis``.

    ``launch(q, c, *, qmap, cmap)`` is a family's ``estimate_fields``; ``q``
    is its tuple of query components (replicated), ``c`` its tuple of
    ``[C, P, ...]`` corpus components, each split along the row axis.  P
    must be a multiple of the axis size, as a sharded
    :class:`repro.data.store.CorpusStore` keeps its capacity.  Returns
    ``[G, Q, P]`` f32, bitwise identical to ``launch(q, c, ...)``.
    """
    d = mesh.shape[axis]
    rows = c[0].shape[1]
    if rows % d:
        raise ValueError(f"{rows} corpus rows do not split over {d} shards")
    f = _corpus_sharded_fn(launch, tuple(qmap), tuple(cmap), mesh, axis)
    return f(q, c)


def _ordered_top_k(score, k: int):
    """``jax.lax.top_k`` over the last dim, ties broken by ascending index.

    ``lax.top_k`` leaves the order of equal scores to the backend, and a
    TPU orders them differently for different batch shapes, so a query's
    candidates would depend on the micro-batch it came in.  Every row
    scoring above the k-th score is in the top k; of the rows tied with
    it, the lowest indices fill the rest.  A second ``top_k`` over an int32
    key picks exactly that set, and a sort of the k winners by (score
    descending, index ascending) fixes their order.
    """
    n = score.shape[-1]
    kth = jax.lax.top_k(score, k)[0][..., -1:]
    pos = jax.lax.broadcasted_iota(jnp.int32, score.shape, score.ndim - 1)
    key = jnp.where(score > kth, n, jnp.where(score == kth, n - 1 - pos, -1))
    idx = jax.lax.top_k(key, k)[1]
    neg, idx = jax.lax.sort(
        (-jnp.take_along_axis(score, idx, axis=-1), idx), num_keys=2)
    return -neg, idx


@_obs.instrumented("top_k")
@functools.partial(jax.jit, static_argnames=("k",))
def top_k(score, k: int):
    """Top-k scores and indices over the last dim of ``score``, ties broken
    by ascending index on every backend (see :func:`_ordered_top_k`)."""
    return _ordered_top_k(score, k)


@_obs.instrumented("sharded_top_k")
def sharded_top_k(score, k: int, *, mesh, axis="data"):
    """Per-shard top-k over the last dim of ``score``, merged globally.

    Bitwise identical -- values AND indices -- to :func:`top_k`: each
    shard's candidate list keeps ascending global indices within equal
    scores, and the merge concatenates shards in index order, so the global
    re-ranking resolves ties exactly as the unsharded call does.  Any
    global top-k row must be in its own shard's top-k (rows ranked above it
    locally are ranked above it globally), so per-shard k candidates always
    suffice.
    """
    d = mesh.shape[axis]
    n = score.shape[-1]
    pad = (-n) % d
    # pad below every real score (the ranking floor is -1), never selected
    score = _pad_corpus_rows(score, pad, score.ndim - 1, -jnp.inf)
    shard = score.shape[-1] // d
    kl = min(k, shard)
    f = _sharded_topk_fn(mesh, axis, kl, shard, score.ndim)
    vals, idx = f(score)
    v, pos = _ordered_top_k(vals, k)
    return v, jnp.take_along_axis(idx, pos, axis=-1)


@_obs.instrumented("take_candidates")
@functools.partial(jax.jit, static_argnames=("planes",))
def take_candidates(est, idx, *, planes):
    """The ranked candidates' estimates, gathered on the device.

    ``est`` is a ``[G, Q, P]`` stack of estimate planes and ``idx`` the
    ``[Q, k]`` candidate indices :func:`top_k` or :func:`sharded_top_k`
    picked from it; returns one ``[Q, k]`` array per plane ``g`` in
    ``planes``, bit for bit ``est[g][q, idx[q]]``.  Only these small
    arrays need leave the device, never a ``[Q, P]`` plane.
    """
    return tuple(jnp.take_along_axis(est[g], idx, axis=-1) for g in planes)


@functools.lru_cache(maxsize=None)
def _sharded_topk_fn(mesh, axis: str, kl: int, shard: int, ndim: int):
    def body(s):
        v, i = _ordered_top_k(s, kl)
        return v, i + jax.lax.axis_index(axis) * shard

    spec = PSpec(*([None] * (ndim - 1) + [axis]))
    return compat.shard_map(body, mesh=mesh, in_specs=(spec,),
                            out_specs=(spec, spec))
