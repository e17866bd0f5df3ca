"""DMH (densified one-permutation weighted MinHash) contract tests.

The constant-time ingest family must honour four contracts at once:

  * the Pallas kernel is a bit-twin of the jnp reference and of the numpy
    host oracle (:class:`repro.core.dmh.DMH`) on the shared u32 streams --
    mixed host/device corpora keep colliding;
  * densification fills every empty bin deterministically, including the
    adversarial 1-nonzero vector where m - 1 of m bins start empty;
  * collision probability stays an unbiased weighted-Jaccard estimate --
    binning plus uniform reseeded borrowing must not re-introduce the bias
    of the rotation-densified 2014 scheme (pinned against the exact ICWS
    oracle over many seeds);
  * union-merge of disjoint-support shards commutes bitwise and matches
    the host oracle, and packed (bf16-halfword) storage round-trips with
    inert spare rows -- DMH rows ride the ICWS wire layout unchanged.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SparseVec
from repro.core import dmh as host_dmh
from repro.core import u32
from repro.core.dmh import DMH
from repro.core.icws import ICWS
from repro.data import make_family, wmh_storage
from repro.kernels import common as kcommon
from repro.kernels import ops
from repro.kernels.dmh_sketch import dmh_sketch_pallas, dmh_sketch_scatter
from repro.kernels.packed import pack_halfwords_f32, unpack_halfwords_f32
from repro.kernels.ref import dmh_sketch_ref


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------
def _rand_batch(rng, B, N, density=1.0):
    """Padded [B, N] (w, keys, vals) device arrays + per-row SparseVecs."""
    keys = np.zeros((B, N), np.int32)
    vals = np.zeros((B, N), np.float32)
    w = np.zeros((B, N), np.float32)
    vecs = []
    for b in range(B):
        nnz = max(1, int(N * density))
        idx = rng.choice(2**31 - 1, size=nnz, replace=False).astype(np.int64)
        x = rng.normal(size=nnz)
        v = SparseVec.from_pairs(idx, x, 2**31)
        vecs.append(v)
        z = (v.values / v.norm()).astype(np.float32)
        keys[b, :nnz] = v.indices.astype(np.int32)
        vals[b, :nnz] = z
        w[b, :nnz] = z * z
    return jnp.asarray(w), jnp.asarray(keys), jnp.asarray(vals), vecs


def _f1(comps):
    """Stack F=1: [B, ...] components -> [1, B, ...] (estimate_fields)."""
    return tuple(jnp.asarray(c)[None] for c in comps)


def _assert_sketches_match(got, want, amin_rtol=1e-5):
    """fp/argkey bit-exact; val to f32 rounding; amin looser (eager jnp vs
    jitted interpret transcendentals differ in the last ulp or two)."""
    fp_g, val_g, amin_g, key_g = (np.asarray(x) for x in got[:4])
    fp_w, val_w, amin_w, key_w = (np.asarray(x) for x in want[:4])
    assert np.array_equal(fp_g, fp_w)
    assert np.array_equal(key_g, key_w)
    np.testing.assert_allclose(val_g, val_w, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(amin_g, amin_w, rtol=amin_rtol)


# ---------------------------------------------------------------------------
# probe budget: host and device MUST agree or borrowed bins stop colliding
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m", [1, 2, 31, 32, 64, 66, 128, 200, 266, 1024,
                               4096])
def test_densify_probe_budget_twins(m):
    assert host_dmh.densify_probes(m) == kcommon.densify_probes(m)
    assert kcommon.densify_probes(m) % 128 == 0
    assert kcommon.densify_probes(m) <= 1024


# ---------------------------------------------------------------------------
# kernel vs jnp reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,N,m,seed", [(3, 64, 64, 0),
                                        (5, 300, 200, 7),     # padded odd-ish m
                                        (2, 1024, 266, 3),    # bench sizes
                                        (8, 100, 64, 11)])
def test_kernel_matches_ref(B, N, m, seed):
    rng = np.random.default_rng(B * 1000 + N + m + seed)
    w, keys, vals, _ = _rand_batch(rng, B, N)
    ref = dmh_sketch_ref(w, keys, vals, m=m, seed=seed)
    bm = 128 * (-(-m // 128))
    got = dmh_sketch_pallas(w, keys, vals, m=m, seed=seed, bm=bm,
                            interpret=True)
    _assert_sketches_match(got, ref)


@pytest.mark.parametrize("B,N,m,seed", [(3, 64, 64, 0),
                                        (5, 300, 200, 7),
                                        (8, 100, 64, 11)])
def test_scatter_lowering_matches_kernel(B, N, m, seed):
    """The O(nnz + m) scatter builder ops dispatches to off-TPU is the
    same computation as the Pallas kernel: fingerprints / argkeys bitwise,
    values / minima to transcendental rounding."""
    rng = np.random.default_rng(B * 77 + N + m + seed)
    w, keys, vals, _ = _rand_batch(rng, B, N)
    kernel = dmh_sketch_pallas(w, keys, vals, m=m, seed=seed,
                               bm=128 * (-(-m // 128)), interpret=True)
    scatter = dmh_sketch_scatter(w, keys, vals, m=m, seed=seed)
    _assert_sketches_match(scatter, kernel)
    # and ops.dmh_sketch resolves to one of the two (interpret dispatch)
    via_ops = ops.dmh_sketch(w, keys, vals, m=m, seed=seed)
    _assert_sketches_match(via_ops, kernel)


@pytest.mark.slow
def test_kernel_block_shape_invariant():
    """fp/val/key planes are bitwise identical for every (br, bm, bn)."""
    rng = np.random.default_rng(21)
    B, N, m, seed = 6, 700, 64, 5
    w, keys, vals, _ = _rand_batch(rng, B, N)
    base = dmh_sketch_pallas(w, keys, vals, m=m, seed=seed, br=1, bm=128,
                             bn=256, interpret=True)
    for br, bm, bn in [(2, 128, 256), (3, 256, 512), (6, 128, 1024),
                      (1, 384, 128)]:
        got = dmh_sketch_pallas(w, keys, vals, m=m, seed=seed, br=br, bm=bm,
                                bn=bn, interpret=True)
        for g, b in zip(got, base):
            assert np.array_equal(np.asarray(g), np.asarray(b)), \
                f"block shape ({br},{bm},{bn}) changed the sketch"


# ---------------------------------------------------------------------------
# host oracle vs device kernel: interoperable fingerprints
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("nnz,m,seed", [(64, 64, 0), (300, 128, 7),
                                        (1000, 266, 3)])
def test_host_device_fingerprints_compatible(nnz, m, seed):
    rng = np.random.default_rng(nnz + m + seed)
    idx = rng.choice(2**31 - 1, size=nnz, replace=False).astype(np.int64)
    v = SparseVec.from_pairs(idx, rng.normal(size=nnz), 2**31)
    host = DMH(m=m, seed=seed).sketch(v)

    # the device pad expands keys into pseudo-key replicas exactly like
    # the host oracle (m = 128 -> c = 2, m = 266 -> c = 4); raw kernel
    # inputs must go through the same shared expansion
    z32 = (v.values / v.norm()).astype(np.float32)
    c = host_dmh.dmh_replication(m)
    kk = host_dmh.replicate_keys(
        v.indices.astype(np.int64).astype(np.uint32), c)
    z_r = np.tile(z32, c)
    w = jnp.asarray((z_r * z_r)[None, :])
    keys = jnp.asarray(kk.view(np.int32)[None, :])
    vals = jnp.asarray(z_r[None, :])
    fp, val, _, key = ops.dmh_sketch(w, keys, vals, m=m, seed=seed)
    fp_dev, val_dev = np.asarray(fp)[0], np.asarray(val)[0]

    agree = np.mean(host.fingerprints == fp_dev)
    assert agree > 0.99, f"fingerprint agreement {agree:.4f}"
    same = host.fingerprints == fp_dev
    np.testing.assert_allclose(host.values[same], val_dev[same],
                               rtol=1e-5, atol=1e-6)
    assert host.fingerprints.dtype == np.int32
    assert (host.fingerprints >= -1).all()          # 31-bit fp or empty
    # argkeys witness origins identically where fingerprints agree
    assert np.array_equal(np.asarray(host.argkeys)[same],
                          np.asarray(key)[0][same])


# ---------------------------------------------------------------------------
# pseudo-key replication (m > 64): formula, expansion, ingest consistency
# ---------------------------------------------------------------------------
def test_replication_formula_and_salts():
    """c = clamp(m // 64, 1, 4): identity below m = 128, capped at 4
    (pseudo-keys of different keys can alias, k1 ^ r1*SALT == k2 ^
    r2*SALT, and the alias odds grow ~c^2 -- see dmh_replication)."""
    got = {m: host_dmh.dmh_replication(m)
           for m in (1, 64, 66, 127, 128, 191, 266, 512)}
    assert got == {1: 1, 64: 1, 66: 1, 127: 1, 128: 2, 191: 2, 266: 4,
                   512: 4}
    s = host_dmh.replica_salts(4)
    assert s.dtype == np.uint32
    assert s[0] == 0                        # replica 0 is the identity
    assert np.unique(s).size == 4
    kk = np.arange(5, dtype=np.uint32) + 7
    rep = host_dmh.replicate_keys(kk, 3)
    assert rep.shape == (15,)
    assert np.array_equal(rep[:5], kk)      # replica-major, r = 0 first
    # batched expansion == per-row expansion (the ingest pad uses [B, N])
    kb = (np.arange(10, dtype=np.uint32)
          * np.uint32(2654435761)).reshape(2, 5)
    repb = host_dmh.replicate_keys(kb, 3)
    assert repb.shape == (2, 15)
    for b in range(2):
        assert np.array_equal(repb[b], host_dmh.replicate_keys(kb[b], 3))


def test_replicated_ingest_matches_host_oracle():
    """m = 160 (c = 2): the family ingest pad and the host oracle expand
    through the shared replicate_keys, so fingerprints still collide and
    stored argkeys (pseudo-keys) witness identical origins."""
    rng = np.random.default_rng(17)
    m = 160
    fam = make_family("dmh", storage=int(1.5 * m + 1), seed=13)
    assert fam.m == m
    vecs = []
    for _ in range(4):
        idx = rng.choice(2**31 - 1, size=300, replace=False)
        vecs.append(SparseVec.from_pairs(np.sort(idx),
                                         rng.normal(size=300), 2**31))
    fp_d, _, _, key_d = (np.asarray(x) for x in fam.sketch_rows(vecs))
    host = DMH(m=m, seed=13)
    for b, v in enumerate(vecs):
        s = host.sketch(v)
        agree = s.fingerprints == fp_d[b]
        assert agree.mean() > 0.99
        assert np.array_equal(np.asarray(s.argkeys)[agree], key_d[b][agree])


def test_single_nonzero_densifies_every_bin():
    """Adversarial emptiness: 1 nonzero at m=64 leaves 63 empty bins; the
    densification epilogue must copy the lone winner everywhere, host and
    device alike."""
    m, seed = 64, 9
    v = SparseVec.from_pairs(np.array([123456789]), np.array([2.5]), 2**31)
    host = DMH(m=m, seed=seed).sketch(v)
    assert (host.fingerprints >= 0).all()
    assert np.unique(host.fingerprints).size == 1
    assert (np.asarray(host.argkeys).view(np.uint32) == 123456789).all()
    np.testing.assert_allclose(host.values, 1.0, rtol=1e-6)  # z = v / |v|

    w = jnp.asarray([[1.0]], jnp.float32)
    keys = jnp.asarray([[123456789]], jnp.int32)
    vals = jnp.asarray([[1.0]], jnp.float32)
    fp, val, amin, key = ops.dmh_sketch(w, keys, vals, m=m, seed=seed)
    assert np.array_equal(np.asarray(fp)[0], host.fingerprints)
    assert (np.asarray(key)[0] == 123456789).all()
    assert (np.asarray(amin)[0] < np.float32(host_dmh._BIG
            if hasattr(host_dmh, "_BIG") else 1e30)).all()


def test_empty_row_kernel_sentinels():
    """All-zero rows produce the ICWS empty wire sentinels the estimate
    kernels treat as zero-overlap: fp = -1, val = 0, argkey = 0."""
    m = 64
    fp, val, _, key = ops.dmh_sketch(jnp.zeros((2, 32)), jnp.zeros(
        (2, 32), jnp.int32), jnp.zeros((2, 32)), m=m, seed=0)
    assert (np.asarray(fp) == -1).all()
    assert (np.asarray(val) == 0).all()
    assert (np.asarray(key) == 0).all()

    host = DMH(m=m, seed=0).sketch(SparseVec.from_pairs(
        np.zeros(0, np.int64), np.zeros(0), 2**31))
    assert (host.fingerprints == -1).all()
    assert (host.values == 0).all()


# ---------------------------------------------------------------------------
# statistical contract: collision probability is unbiased weighted Jaccard
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_collision_probability_unbiased_vs_icws():
    """Over 400 seeds, the mean DMH collision rate on a known-Jaccard pair
    must match the exact-ICWS collision rate within 4 combined standard
    errors.  Constant-value vectors with 30 of 60 keys shared give
    weighted Jaccard exactly 1/3; a biased densification (the 2014
    rotation scheme) fails this by many sigma at m = 64."""
    m, seeds = 64, 400
    rng = np.random.default_rng(1234)
    keys = rng.choice(2**31 - 1, size=90, replace=False).astype(np.int64)
    va = SparseVec.from_pairs(np.sort(keys[:60]), np.ones(60), 2**31)
    vb = SparseVec.from_pairs(np.sort(keys[30:]), np.ones(60), 2**31)
    jac = 30.0 / 90.0

    rates = {"dmh": np.empty(seeds), "icws": np.empty(seeds)}
    for cls, name in ((DMH, "dmh"), (ICWS, "icws")):
        for s in range(seeds):
            sk = cls(m=m, seed=s)
            sa, sb = sk.sketch(va), sk.sketch(vb)
            rates[name][s] = np.mean(sa.fingerprints == sb.fingerprints)

    sem = np.sqrt(rates["dmh"].var() / seeds + rates["icws"].var() / seeds)
    diff = abs(rates["dmh"].mean() - rates["icws"].mean())
    assert diff <= 4 * sem, (
        f"dmh {rates['dmh'].mean():.4f} vs icws {rates['icws'].mean():.4f} "
        f"(J = {jac:.4f}): |diff| = {diff:.4f} > 4 SEM = {4 * sem:.4f}")
    # and both track the analytic Jaccard
    icws_sem = rates["icws"].std() / np.sqrt(seeds)
    assert abs(rates["icws"].mean() - jac) <= 5 * icws_sem


# ---------------------------------------------------------------------------
# packed storage: bf16-halfword epilogue + roundtrip + inert spare rows
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m", [64, 65])        # even and odd widths
def test_pack_vals_epilogue_bitwise(m):
    rng = np.random.default_rng(m)
    w, keys, vals, _ = _rand_batch(rng, 3, 200)
    fp, val, amin, key, packed = dmh_sketch_pallas(w, keys, vals, m=m,
                                                   seed=2, pack_vals=True,
                                                   interpret=True)
    want = pack_halfwords_f32(jnp.asarray(val))
    assert packed.shape == (3, m)
    assert np.array_equal(np.asarray(packed), np.asarray(want))
    # roundtrip: unpack reproduces the bf16 truncation of val exactly
    back = np.asarray(unpack_halfwords_f32(packed))
    np.testing.assert_allclose(back, np.asarray(val), rtol=1 / 128.0,
                               atol=1e-6)
    assert np.array_equal(np.asarray(pack_halfwords_f32(jnp.asarray(back))),
                          np.asarray(packed))
    # the scatter lowering's packed plane packs ITS val plane identically
    outs_s = dmh_sketch_scatter(w, keys, vals, m=m, seed=2, pack_vals=True)
    want_s = pack_halfwords_f32(jnp.asarray(outs_s[1]))
    assert np.array_equal(np.asarray(outs_s[4]), np.asarray(want_s))


def test_packed_store_spare_rows_inert_for_dmh():
    """A DMH corpus in packed storage estimates identically before and
    after growing spare capacity -- spare rows stay bitwise inert."""
    fam = make_family("dmh", storage=wmh_storage(64), seed=5)
    rng = np.random.default_rng(55)
    _, _, _, vecs = _rand_batch(rng, 6, 120)
    qf = fam.sketch_rows(vecs[:2])
    cf = tuple(jnp.asarray(x) for x in fam.sketch_rows(vecs))

    base = np.asarray(fam.estimate_fields(_f1(qf), _f1(cf),
                                          qmap=(0,), cmap=(0,))[0])
    # spare rows: zero-extended components (the packed store's pad layout)
    pad = 4
    cf_pad = tuple(jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
                   for x in cf)
    grown = np.asarray(fam.estimate_fields(_f1(qf), _f1(cf_pad), qmap=(0,),
                                           cmap=(0,))[0])
    assert np.array_equal(grown[:, :base.shape[1]], base)
    assert (grown[:, base.shape[1]:] == 0).all()


# ---------------------------------------------------------------------------
# union-merge: disjoint shards, bitwise commutative, host-oracle-exact
# ---------------------------------------------------------------------------
def _disjoint_split(rng, nnz=160, m_ambient=2**31):
    """One vector split into a disjoint-support partition (the merge
    contract's precondition)."""
    idx = rng.choice(m_ambient - 1, size=nnz, replace=False).astype(np.int64)
    x = rng.normal(size=nnz)
    mask = rng.random(nnz) < 0.5
    full = SparseVec.from_pairs(idx, x, m_ambient)
    left = SparseVec.from_pairs(idx[mask], x[mask], m_ambient)
    right = SparseVec.from_pairs(idx[~mask], x[~mask], m_ambient)
    return full, left, right


def _family_fields(fam, vecs):
    return tuple(jnp.asarray(x) for x in fam.sketch_rows(vecs))


@pytest.mark.parametrize("seed,base_m", [(0, 64), (7, 64), (3, 256)])
def test_merge_rows_matches_host_oracle(seed, base_m):
    # base_m = 256 exercises c = 4 pseudo-key replication end to end:
    # merge operates on stored pseudo-key argkeys and needs no expansion
    fam = make_family("dmh", storage=wmh_storage(base_m), seed=seed)
    oracle = fam.host_oracle()
    rng = np.random.default_rng(60 + seed)
    full, left, right = _disjoint_split(rng)

    fa = _family_fields(fam, [left])
    fb = _family_fields(fam, [right])
    merged = fam.merge_rows(fa, fb)
    fp_m, val_m, norm_m, key_m = (np.asarray(x) for x in merged)

    host = oracle.merge(oracle.sketch(left), oracle.sketch(right))
    assert np.array_equal(fp_m[0], host.fingerprints)
    assert np.array_equal(key_m[0], np.asarray(host.argkeys))
    np.testing.assert_allclose(val_m[0], host.values, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(norm_m[0], host.norm, rtol=1e-6)


def test_merge_rows_commutes_bitwise():
    fam = make_family("dmh", storage=wmh_storage(64), seed=3)
    rng = np.random.default_rng(61)
    _, left, right = _disjoint_split(rng)
    fa = _family_fields(fam, [left])
    fb = _family_fields(fam, [right])
    ab = fam.merge_rows(fa, fb)
    ba = fam.merge_rows(fb, fa)
    for x, y in zip(ab, ba):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_merge_one_side_empty_is_exact():
    fam = make_family("dmh", storage=wmh_storage(64), seed=4)
    rng = np.random.default_rng(62)
    _, left, _ = _disjoint_split(rng)
    fa = _family_fields(fam, [left])
    empty = SparseVec.from_pairs(np.zeros(0, np.int64), np.zeros(0), 2**31)
    fe = _family_fields(fam, [empty])
    merged = fam.merge_rows(fa, fe)
    for got, want in zip(merged, fa):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=0)


def test_merged_estimates_track_full_sketch():
    """Inner-product estimates from shard-merged sketches agree with the
    full-vector sketch estimate to sketch noise (not bitwise -- rescored
    merges redraw winners -- but the estimator contract must hold)."""
    fam = make_family("dmh", storage=wmh_storage(256), seed=8)
    rng = np.random.default_rng(63)
    full, left, right = _disjoint_split(rng, nnz=400)
    probe_v = SparseVec.from_pairs(
        np.asarray(full.indices[:200]), np.asarray(full.values[:200]) * 0.7,
        2**31)

    merged = fam.merge_rows(_family_fields(fam, [left]),
                            _family_fields(fam, [right]))
    whole = _family_fields(fam, [full])
    probe = _family_fields(fam, [probe_v])

    est_m = float(np.asarray(fam.estimate_fields(
        _f1(probe), _f1(merged), qmap=(0,), cmap=(0,))[0])[0, 0])
    est_w = float(np.asarray(fam.estimate_fields(
        _f1(probe), _f1(whole), qmap=(0,), cmap=(0,))[0])[0, 0])
    true = float(0.7 * np.sum(np.asarray(full.values[:200]) ** 2))
    scale = abs(true)
    assert abs(est_m - true) <= 0.35 * scale
    assert abs(est_m - est_w) <= 0.5 * scale


# ---------------------------------------------------------------------------
# stream registry: host twins mirror the kernel constants
# ---------------------------------------------------------------------------
def test_dmh_stream_constants_in_sync():
    pairs = [("DMH_BIN_STREAM",), ("DMH_R1_STREAM",), ("DMH_R2_STREAM",),
             ("DMH_C1_STREAM",), ("DMH_C2_STREAM",), ("DMH_BETA_STREAM",),
             ("DMH_FP_STREAM",), ("DMH_DENSIFY_STREAM",)]
    for (name,) in pairs:
        assert getattr(u32, name) == getattr(kcommon, name), name
    # DMH streams collide with no other registered stream
    ids = [getattr(kcommon, n) for (n,) in pairs]
    assert len(set(ids)) == len(ids)
    all_streams = kcommon.streams()
    for i in ids:
        assert i in set(all_streams.values())
