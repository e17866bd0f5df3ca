"""Sampling-sketch subsystem: TS/PS oracles, key-match kernel, serving.

Covers the ISSUE-5 acceptance properties head-on: (a) device kernel vs jnp
ref vs host-oracle parity (fixed-shape smokes fast, hypothesis sweeps
``slow``); (b) unbiasedness of the inverse-inclusion-probability estimator
over seeds on sparse vectors; (c) the fixed-slot layout contract of
``pad_sample_batch`` (pad sentinels, tau semantics, truncation fallback);
(d) family plumbing particulars not already covered by the FAMILY_NAMES-
parameterized suites in ``test_families.py`` (which give ts/ps the
inert-spare-row bitwise test at several fill fractions and the batched ==
sequential service identity for free) and ``test_sharded_query.py``
(sharded == single-device rankings).
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import SparseVec, inner_fast
from repro.core.sampling import (PrioritySamplingU32, SampleSketch,
                                 ThresholdSamplingU32, priority_sample,
                                 sample_probs, threshold_sample, ts_target)
from repro.data import make_family, pad_sample_batch, wmh_storage
from repro.data.synthetic import sparse_pair
from repro.kernels import ref
from repro.kernels.sample_estimate import (SAMPLE_CORPUS_PAD_KEY,
                                           SAMPLE_QUERY_PAD_KEY,
                                           sample_estimate_fields_pallas,
                                           sample_inclusion_probs)


def _random_sample_rows(rng, F, B, m, key_pool: int, pad_key: int):
    """Synthetic padded sample rows: random live prefixes of keys drawn
    from a small pool (so cross-row matches actually happen), random
    values, random positive taus."""
    keys = np.full((F, B, m), pad_key, np.int32)
    vals = np.zeros((F, B, m), np.float32)
    taus = np.zeros((F, B), np.float32)
    for f in range(F):
        for b in range(B):
            live = int(rng.integers(0, min(m, key_pool) + 1))
            k = rng.choice(key_pool, size=live, replace=False)
            keys[f, b, :live] = np.sort(k)
            vals[f, b, :live] = rng.normal(size=live)
            taus[f, b] = rng.uniform(0.1, 5.0) if live else 0.0
    return jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(taus)


# ---------------------------------------------------------------------------
# (a) kernel vs jnp ref vs host oracle
# ---------------------------------------------------------------------------
def test_sample_kernel_matches_ref_smoke():
    rng = np.random.default_rng(0)
    kq, vq, tq = _random_sample_rows(rng, 3, 5, 90, 64, SAMPLE_QUERY_PAD_KEY)
    kc, vc, tc = _random_sample_rows(rng, 3, 9, 90, 64, SAMPLE_CORPUS_PAD_KEY)
    aq, ac = sample_inclusion_probs(vq, tq), sample_inclusion_probs(vc, tc)
    qmap, cmap = (0, 1, 0, 2, 0, 1), (0, 0, 1, 0, 2, 1)
    ek = sample_estimate_fields_pallas(kq, vq, aq, kc, vc, tc,
                                       qmap=qmap, cmap=cmap, interpret=True)
    er = np.asarray(ref.sample_estimate_fields_ref(kq, vq, aq, kc, vc, ac,
                                                   qmap=qmap, cmap=cmap))
    assert ek.shape == (6, 5, 9)
    scale = max(1.0, float(np.max(np.abs(er))))
    np.testing.assert_allclose(np.asarray(ek), er, rtol=1e-4,
                               atol=1e-4 * scale)


@pytest.mark.slow
@settings(max_examples=8, deadline=None)
@given(data=st.data())
def test_sample_kernel_matches_ref(data):
    seed = data.draw(st.integers(0, 2 ** 31 - 1))
    rng = np.random.default_rng(seed)
    F = data.draw(st.integers(1, 3))
    C = data.draw(st.integers(1, 3))
    G = data.draw(st.integers(1, 7))
    qmap = tuple(data.draw(st.integers(0, F - 1)) for _ in range(G))
    cmap = tuple(data.draw(st.integers(0, C - 1)) for _ in range(G))
    Q, P = data.draw(st.integers(1, 10)), data.draw(st.integers(1, 14))
    m = data.draw(st.integers(1, 150))
    pool = data.draw(st.integers(max(1, m), 4 * m))
    kq, vq, tq = _random_sample_rows(rng, F, Q, m, pool,
                                     SAMPLE_QUERY_PAD_KEY)
    kc, vc, tc = _random_sample_rows(rng, C, P, m, pool,
                                     SAMPLE_CORPUS_PAD_KEY)
    aq, ac = sample_inclusion_probs(vq, tq), sample_inclusion_probs(vc, tc)
    ek = sample_estimate_fields_pallas(kq, vq, aq, kc, vc, tc,
                                       qmap=qmap, cmap=cmap, interpret=True)
    er = np.asarray(ref.sample_estimate_fields_ref(kq, vq, aq, kc, vc, ac,
                                                   qmap=qmap, cmap=cmap))
    assert ek.shape == (G, Q, P)
    scale = max(1.0, float(np.max(np.abs(er))))
    np.testing.assert_allclose(np.asarray(ek), er, rtol=1e-4,
                               atol=1e-4 * scale)


@pytest.mark.parametrize("name", ["ts", "ps"])
def test_sample_device_estimates_match_host_oracle(name):
    """Device key-match estimates over pad_sample_batch rows == core.sampling
    host-oracle estimates to 1e-5 relative, with sketches built by the same
    selection code but estimated independently (host f64 intersect1d vs
    device f32 Pallas contraction)."""
    fam = make_family(name, storage=wmh_storage(256), seed=9)
    oracle = fam.host_oracle()
    rng = np.random.default_rng(11)
    corpus = [sparse_pair(rng, n=2000, nnz=300, overlap=0.2)[0]
              for _ in range(7)]
    queries = [sparse_pair(rng, n=2000, nnz=300, overlap=0.2)[0]
               for _ in range(4)]
    dev = np.asarray(fam.estimate_fields(
        tuple(c[None] for c in fam.sketch_rows(queries)),
        tuple(c[None] for c in fam.sketch_rows(corpus)),
        qmap=(0,), cmap=(0,))[0], np.float64)               # [Q, P]
    host = np.array([[oracle.estimate(oracle.sketch(q), oracle.sketch(c))
                      for c in corpus] for q in queries])
    scale = float(np.max(np.abs(host)))
    assert scale > 0
    np.testing.assert_allclose(dev, host, rtol=1e-5, atol=1e-5 * scale)


# ---------------------------------------------------------------------------
# (b) unbiasedness of the inverse-probability estimator over seeds
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cls", [ThresholdSamplingU32, PrioritySamplingU32])
def test_sample_estimator_unbiased_over_seeds(cls):
    """Mean estimate over independent hash seeds concentrates on the true
    inner product (within 4 standard errors) in a regime where sampling is
    real: nnz far above the slot count, so most inclusion probabilities
    are strictly below 1."""
    rng = np.random.default_rng(1)
    a, b = sparse_pair(rng, n=3000, nnz=500, overlap=0.3)
    true = inner_fast(a, b)
    ests = []
    for seed in range(400):
        o = cls(slots=64, seed=seed)
        sa, sb = o.sketch(a), o.sketch(b)
        assert sa.keys.size <= 64 and sb.keys.size <= 64
        ests.append(o.estimate(sa, sb))
    ests = np.array(ests)
    sem = ests.std(ddof=1) / np.sqrt(len(ests))
    assert abs(ests.mean() - true) < 4 * sem, (ests.mean(), true, sem)
    # the regime check: sampling actually happened (non-trivial taus)
    assert sa.tau > 0 and (sample_probs(sa.values, sa.tau, 64) < 1).any()


# ---------------------------------------------------------------------------
# (c) fixed-slot layout contract
# ---------------------------------------------------------------------------
def test_pad_sample_batch_layout():
    rng = np.random.default_rng(5)
    vecs = [sparse_pair(rng, n=1000, nnz=200, overlap=0.1)[0],
            SparseVec.from_pairs(np.arange(10), np.ones(10), 1000),
            SparseVec.from_pairs(np.zeros(0, np.int64), np.zeros(0), 1000)]
    slots = 48
    for method in ("ts", "ps"):
        keys, vals, taus = pad_sample_batch(vecs, slots=slots, method=method,
                                            seed=3)
        assert keys.shape == (3, slots) and vals.shape == (3, slots)
        assert keys.dtype == np.int32 and vals.dtype == np.float32
        assert taus.shape == (3,) and taus.dtype == np.float32
        for b in range(3):
            live = keys[b] != SAMPLE_QUERY_PAD_KEY
            n_live = int(live.sum())
            # live entries form an ascending-key prefix; pads carry value 0
            assert np.all(live[:n_live]) and not np.any(live[n_live:])
            assert np.all(np.diff(keys[b, :n_live]) > 0)
            assert np.all(keys[b, :n_live] >= 0)
            assert np.all(vals[b, n_live:] == 0.0)
        # the 10-nnz vector fits whole; the empty vector is all-pad
        assert (keys[1] != SAMPLE_QUERY_PAD_KEY).sum() == 10
        assert np.all(keys[2] == SAMPLE_QUERY_PAD_KEY) and taus[2] == 0.0
        # ps keeps the whole support => probability-1 sentinel tau
        if method == "ps":
            assert taus[1] == 0.0
    with pytest.raises(ValueError):
        pad_sample_batch(vecs, slots=slots, method="bogus")
    with pytest.raises(ValueError):
        pad_sample_batch(vecs, slots=slots, method="ps", target=10)


def test_threshold_overflow_truncates_to_slots():
    """With the target forced above the slot count, threshold sampling's
    overflow fallback must clamp the sample to the layout size (keeping
    the smallest h/p ranks)."""
    rng = np.random.default_rng(8)
    idx = rng.choice(100_000, size=200, replace=False)
    vals = rng.normal(size=200)
    k, v, tau = threshold_sample(idx, vals, slots=16, seed=0, target=200)
    assert k.size == 16
    assert tau == pytest.approx(float(np.sum(vals * vals)) * 16 / 200)
    # the default target leaves two-sigma slack below the slot count
    assert ts_target(256) == 256 - 32


def test_priority_sample_fixed_size_and_tau():
    rng = np.random.default_rng(9)
    idx = rng.choice(100_000, size=300, replace=False)
    vals = rng.normal(size=300) + 0.1
    k, v, tau = priority_sample(idx, vals, slots=32, seed=4)
    assert k.size == 32 and tau > 0
    # every kept coordinate's conditional inclusion probability is the
    # stored-layout reconstruction, and none exceeds 1
    p = sample_probs(v, tau, 32)
    assert np.all((p > 0) & (p <= 1))
    # whole support fits => everything kept with probability 1
    k2, v2, tau2 = priority_sample(idx[:20], vals[:20], slots=32, seed=4)
    assert k2.size == 20 and tau2 == 0.0
    assert np.all(sample_probs(v2, tau2, 32) == 1.0)


def test_sampling_coordination_and_key_folding():
    """Two sketches built independently agree on sampled keys (the
    coordinated hash) and raw indices fold into the 31-bit key domain --
    the same coordinate never lands under two different keys."""
    o = PrioritySamplingU32(slots=8, seed=5)
    idx = np.array([3, 1 << 40 | 3, 7, 11])   # 1<<40|3 folds onto key 3
    s = o.sketch(SparseVec.from_pairs(idx, np.ones(4), 1 << 50))
    assert s.keys.size == 3                   # folded duplicates aggregated
    assert set(s.keys.tolist()) == {3, 7, 11}
    assert float(s.values[s.keys == 3][0]) == 2.0
    # shared support sampled under the same seed matches key-for-key
    a = SparseVec.from_pairs(np.arange(50), np.ones(50), 1000)
    oa = ThresholdSamplingU32(slots=16, seed=6)
    sa, sb = oa.sketch(a), oa.sketch(a)
    np.testing.assert_array_equal(sa.keys, sb.keys)


def test_sample_sketch_storage_accounting():
    s = SampleSketch(keys=np.arange(3), values=np.ones(3), tau=1.0, slots=64)
    assert s.storage_doubles() == 65.0
    fam = make_family("ts", storage=100, seed=0)
    assert fam.slots == 99 and fam.storage_doubles_per_row() == 100.0
