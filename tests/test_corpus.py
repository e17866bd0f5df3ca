"""Device-resident sketch corpus + one-vs-many estimation path.

Covers: the one-vs-many Pallas kernel vs its jnp oracle and vs the pairwise
kernel on a tiled query; SketchCorpus chunked append semantics; the device
corpus-query path against the host ICWS estimator on identical sketches
(1e-5 relative); the rewired DatasetSearchIndex (device vs host-oracle
agreement, duplicate-key ingestion); and the serving front-end.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ICWS, SparseVec
from repro.core.icws import StackedICWS
from repro.data import DatasetSearchIndex, SketchCorpus, sketch_batch
from repro.data.synthetic import sparse_pair
from repro.kernels import ops, ref
from repro.kernels.estimate import (estimate_one_vs_many_pallas,
                                    estimate_partials_pallas)
from repro.serve import SketchSearchService


# ---------------------------------------------------------------------------
# one-vs-many kernel: vs oracle, vs pairwise kernel on a tiled query
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("P,m", [(8, 128), (5, 100), (16, 512), (1, 64),
                                 (9, 130)])
def test_one_vs_many_kernel_matches_ref(P, m):
    rng = np.random.default_rng(P * 37 + m)
    fq = rng.integers(0, 50, size=(1, m)).astype(np.int32)
    fpc = rng.integers(0, 50, size=(P, m)).astype(np.int32)
    vq = rng.normal(size=(1, m)).astype(np.float32)
    vc = rng.normal(size=(P, m)).astype(np.float32)
    cnt_k, sw_k = estimate_one_vs_many_pallas(
        jnp.asarray(fq), jnp.asarray(vq), jnp.asarray(fpc), jnp.asarray(vc),
        interpret=True)
    cnt_r, sw_r = ref.estimate_one_vs_many_ref(
        jnp.asarray(fq), jnp.asarray(vq), jnp.asarray(fpc), jnp.asarray(vc))
    np.testing.assert_allclose(np.asarray(cnt_k), np.asarray(cnt_r))
    np.testing.assert_allclose(np.asarray(sw_k), np.asarray(sw_r), rtol=1e-4)


def test_one_vs_many_equals_pairwise_on_tiled_query():
    """Broadcasting the query in-kernel == materializing the [P, m] tile."""
    rng = np.random.default_rng(3)
    P, m = 12, 256
    fq = rng.integers(0, 30, size=(1, m)).astype(np.int32)
    vq = rng.normal(size=(1, m)).astype(np.float32)
    fpc = rng.integers(0, 30, size=(P, m)).astype(np.int32)
    vc = rng.normal(size=(P, m)).astype(np.float32)
    cnt_b, sw_b = estimate_one_vs_many_pallas(
        jnp.asarray(fq), jnp.asarray(vq), jnp.asarray(fpc), jnp.asarray(vc),
        interpret=True)
    tiled_f = jnp.asarray(np.repeat(fq, P, axis=0))
    tiled_v = jnp.asarray(np.repeat(vq, P, axis=0))
    cnt_p, sw_p = estimate_partials_pallas(tiled_f, tiled_v,
                                           jnp.asarray(fpc), jnp.asarray(vc),
                                           interpret=True)
    np.testing.assert_allclose(np.asarray(cnt_b), np.asarray(cnt_p))
    np.testing.assert_allclose(np.asarray(sw_b), np.asarray(sw_p), rtol=1e-5)


def test_one_vs_many_empty_query_guard():
    """An all-empty query sketch (fp == -1) collides with nothing."""
    P, m = 4, 128
    fq = jnp.full((1, m), -1, jnp.int32)
    vq = jnp.zeros((1, m))
    fpc = jnp.full((P, m), -1, jnp.int32)     # empty corpus rows too
    vc = jnp.zeros((P, m))
    cnt, sw = estimate_one_vs_many_pallas(fq, vq, fpc, vc, interpret=True)
    assert np.all(np.asarray(cnt) == 0.0)
    assert np.all(np.asarray(sw) == 0.0)


# ---------------------------------------------------------------------------
# SketchCorpus: chunked append, no restacking, device-vs-host estimates
# ---------------------------------------------------------------------------
def _lake_vecs(rng, count, n=600, nnz=150):
    vecs = []
    for _ in range(count):
        a, b = sparse_pair(rng, n=n, nnz=nnz, overlap=0.3)
        vecs.append(a)
    return vecs


def test_corpus_chunked_append_matches_one_shot():
    rng = np.random.default_rng(17)
    vecs = _lake_vecs(rng, 7)
    m = 128
    one = SketchCorpus(m=m, seed=5)
    one.add_batch(vecs)
    chunked = SketchCorpus(m=m, seed=5)
    chunked.add_batch(vecs[:3])
    chunked.add_batch(vecs[3:5])
    chunked.add_batch(vecs[5:])
    assert len(one) == len(chunked) == 7
    fp1, v1, n1, k1 = one.arrays()
    fp2, v2, n2, k2 = chunked.arrays()
    assert np.array_equal(np.asarray(fp1), np.asarray(fp2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2))
    np.testing.assert_allclose(np.asarray(n1), np.asarray(n2))
    assert np.array_equal(np.asarray(k1), np.asarray(k2))
    # appends land in the canonical store: rows already written are stable
    # across later appends (and capacity growth), no chunk re-consolidation
    assert chunked.capacity >= len(chunked)
    chunked.add_batch(vecs[:1])
    assert len(chunked) == 8
    fp3, _, _, _ = chunked.arrays()
    assert np.array_equal(np.asarray(fp3)[:7], np.asarray(fp2))


def test_corpus_device_query_matches_host_estimator_on_identical_sketches():
    """The acceptance bar: one-vs-many device estimates == host ICWS
    estimate_batch on the same sketch arrays, to 1e-5 relative."""
    rng = np.random.default_rng(23)
    vecs = _lake_vecs(rng, 9)
    q, _ = sparse_pair(rng, n=600, nnz=150, overlap=0.3)
    m = 256
    corpus = SketchCorpus(m=m, seed=2)
    corpus.add_batch(vecs)
    fq, vq, nq, _ = corpus.sketch_query(q)
    dev = np.asarray(corpus.estimate(fq, vq, nq[0]), np.float64)

    # identical sketches, host estimator (f64), query tiled host-side
    fpc, vc, nc = (np.asarray(a) for a in corpus.arrays()[:3])
    P = len(vecs)
    A = StackedICWS(fingerprints=np.repeat(np.asarray(fq), P, axis=0),
                    values=np.repeat(np.asarray(vq, np.float64), P, axis=0),
                    norm=np.full(P, float(nq[0]), np.float64))
    B = StackedICWS(fingerprints=fpc, values=vc.astype(np.float64),
                    norm=nc.astype(np.float64))
    host = ICWS(m=m, seed=2).estimate_batch(A, B)
    scale = np.maximum(np.abs(host), np.abs(dev))
    rel = np.abs(dev - host) / np.where(scale == 0, 1.0, scale)
    assert rel.max() < 1e-5, rel


def test_corpus_estimate_accuracy_end_to_end():
    """Device corpus query estimates true inner products (paper band)."""
    rng = np.random.default_rng(29)
    m = 2048
    pairs = [sparse_pair(rng, n=800, nnz=200, overlap=0.4) for _ in range(4)]
    corpus = SketchCorpus(m=m, seed=9)
    corpus.add_batch([b for _, b in pairs])
    from repro.core import inner_fast
    for qi, (a, _) in enumerate(pairs):
        est = np.asarray(corpus.estimate_vec(a))
        true = inner_fast(a, pairs[qi][1])
        bound = 4.0 / np.sqrt(m) * a.norm() * pairs[qi][1].norm()
        assert abs(est[qi] - true) < bound


def test_corpus_empty_raises():
    corpus = SketchCorpus(m=64)
    with pytest.raises(ValueError):
        corpus.arrays()


def test_corpus_add_sketches_validates_all_components():
    """Regression: a mismatched ``val`` (or ``norm``) must fail at ingest.

    Pre-fix, ``add_sketches`` checked only fp-vs-norm row counts and a
    wrong-sized ``val`` sailed in, deferring the failure to query time."""
    rng = np.random.default_rng(3)
    m = 64
    corpus = SketchCorpus(m=m)
    fp = rng.integers(0, 50, size=(4, m)).astype(np.int32)
    val = rng.normal(size=(4, m)).astype(np.float32)
    norm = np.ones(4, np.float32)
    key = rng.integers(0, 2 ** 31 - 1, size=(4, m)).astype(np.int32)
    with pytest.raises(ValueError):
        corpus.add_sketches(fp, val[:3], norm, key)     # short val
    with pytest.raises(ValueError):
        corpus.add_sketches(fp, val, norm[:3], key)     # short norm
    with pytest.raises(ValueError):
        corpus.add_sketches(fp, val, norm, key[:3])     # short argkeys
    assert len(corpus) == 0                             # nothing ingested
    corpus.add_sketches(fp, val, norm, key)             # matched: fine
    assert len(corpus) == 4


# ---------------------------------------------------------------------------
# DatasetSearchIndex: device path vs host oracle, duplicate keys
# ---------------------------------------------------------------------------
def test_dataset_search_device_vs_host_oracle():
    rng = np.random.default_rng(31)
    idx = DatasetSearchIndex(m=768, seed=4)
    keys = np.arange(800)
    signal = rng.normal(size=800)
    idx.add_table("corr", keys, signal + 0.2 * rng.normal(size=800))
    idx.add_table("noise", keys, rng.normal(size=800))
    idx.add_table("disjoint", np.arange(10_000, 10_800),
                  rng.normal(size=800))

    dev = idx.query(keys, signal, top_k=3, min_join=40)
    host = idx.query(keys, signal, top_k=3, min_join=40, backend="host")
    assert [r.name for r in dev] == [r.name for r in host]
    assert dev[0].name == "corr"
    for d, h in zip(dev, host):
        # two unbiased estimators of the same join size; both near truth
        assert abs(d.join_size - h.join_size) < 0.35 * 800
        assert d.corr == h.corr          # KMV refinement is shared


def test_dataset_search_duplicate_keys_regression():
    """Realistic lake table with repeated join keys must ingest and the
    join size must count joined row *pairs* (SQL semantics)."""
    rng = np.random.default_rng(37)
    n_orders = 1200
    customer = rng.integers(0, 200, size=n_orders)        # many repeats
    amount = rng.uniform(10, 500, size=n_orders)
    idx = DatasetSearchIndex(m=1024, seed=6)
    idx.add_table("orders", customer, amount)             # crashed before

    q_keys = np.arange(200)                               # customer dimension
    q_vals = rng.uniform(0, 1, size=200)
    res = idx.query(q_keys, q_vals, top_k=1, min_join=10)
    assert len(res) == 1
    # true join cardinality = number of order rows with customer in 0..199
    true_pairs = float(n_orders)
    assert abs(res[0].join_size - true_pairs) / true_pairs < 0.5
    # the indicator vector carries multiplicities
    ind, val, sq = idx.vectorize(customer, amount)
    assert ind.values.sum() == n_orders
    assert ind.nnz == len(np.unique(customer))
    # aggregated value vector sums duplicates
    first_key = int(ind.indices[0])
    assert np.isclose(val.values[0], amount[customer == first_key].sum())


def test_vectorize_aggregates_keys_colliding_mod_key_space():
    """Two distinct int64 keys that collide mod ``key_space`` must fold and
    aggregate identically in all three field vectors (pre-fix, the signed-
    value vector deduplicated *raw* keys, so colliding keys crashed/dropped
    in ``from_pairs`` while the indicator path aggregated them)."""
    ks = 97
    idx = DatasetSearchIndex(m=64, seed=0, key_space=ks)
    keys = np.array([1, 1 + ks, 5, 5 + 3 * ks, 96])
    vals = np.array([2.0, 3.0, 1.0, 4.0, -1.0])
    ind, val, sq = idx.vectorize(keys, vals)
    for v in (ind, val, sq):
        assert list(v.indices) == [1, 5, 96]          # folded + deduplicated
        assert np.all(v.indices < ks)                 # in the sketch domain
    assert ind.values[0] == 2.0                       # multiplicity of key 1
    assert val.values[0] == 5.0 and val.values[1] == 5.0
    assert sq.values[0] == 2.0 ** 2 + 3.0 ** 2
    # and a colliding table ingests + serves end to end on both paths
    idx.add_table("t", keys, vals)
    res = idx.query(keys, vals, top_k=1, min_join=1)
    host = idx.query(keys, vals, top_k=1, min_join=1, backend="host")
    assert res and host and res[0].name == host[0].name == "t"


def test_dataset_search_zero_values_survive_aggregation():
    keys = np.array([3, 3, 5])
    vals = np.array([1.0, -1.0, 0.0])     # duplicates cancel; explicit zero
    idx = DatasetSearchIndex(m=64, seed=0)
    ind, val, sq = idx.vectorize(keys, vals)
    assert set(ind.indices) == {3, 5}     # both keys represented
    assert set(val.indices) == {3, 5}     # cancellation nudged, not dropped


def test_served_vectors_centre_values_and_keep_raw_sample_values():
    """The value fields are sketched centred on the table's mean (rounded
    to a power-of-two step at or below the deviation, so integers stay
    integers); the KMV sample keeps the raw aggregated values."""
    keys = np.array([3, 3, 5, 9])
    vals = np.array([101.0, 103.0, 98.0, 102.0])
    idx = DatasetSearchIndex(m=64, seed=0)
    (ind, val, sq), raw, shift = idx.served_vectors(keys, vals)
    rind, rval, _ = idx.vectorize(keys, vals)
    assert shift == vals.mean() == 101.0          # std 1.87: step 1
    np.testing.assert_array_equal(ind.values, rind.values)
    # key 3's row at the mean centres to 0, nudged to 1e-9 like any zero
    np.testing.assert_allclose(val.values, [2.0, -3.0, 1.0], rtol=1e-8)
    np.testing.assert_allclose(sq.values, [0.0 + 4.0, 9.0, 1.0], rtol=1e-8)
    np.testing.assert_allclose(raw.values, rval.values, rtol=1e-9)
    _, _, shift = idx.served_vectors(keys, vals * 1000 + 0.3)
    assert shift == 99 * 1024                     # std 1870: step 1024


def test_device_correlation_survives_large_value_levels():
    """The five-estimate correlation of a table 0.6-correlated with the
    query, both at levels 20-100x their spread: over centred fields the
    device score lands near 0.6 (uncentred, ``join * sum(v^2) - sum(v)^2``
    cancels into noise: scores of 0 or 1)."""
    from repro.data.dataset_search import CFIELD, QFIELD, _corr_scores
    days = np.arange(730)
    for seed in range(3):
        rng = np.random.default_rng(seed)
        sig, noise = rng.normal(size=730), rng.normal(size=730)
        idx = DatasetSearchIndex(m=256, seed=1, keep_host_oracle=False)
        idx.add_table("t", days,
                      1e5 * (1 + 0.05 * (0.6 * sig + 0.8 * noise)))
        fields, _, _ = idx.served_vectors(days, 1e3 * (1 + 0.01 * sig))
        q = tuple(jnp.swapaxes(c.reshape((1, 3) + c.shape[1:]), 0, 1)
                  for c in idx.family.sketch_rows(list(fields)))
        est = idx.family.estimate_fields(q, idx.store.buffers(), qmap=QFIELD,
                                         cmap=CFIELD)[:, :, :1]
        score = float(_corr_scores(*est, jnp.float32(0))[0, 0])
        assert abs(score - 0.6) < 0.15, (seed, score)


def test_corr_score_weighs_correlation_by_join_evidence():
    """A perfect correlation over a join just past ``min_join`` must rank
    below a strong one over a large join: with the weight ``join / (join +
    min_join)`` the small join scores ~0.5, the large one ~0.9.  With no
    evidence scale (``min_join <= 0``) the score is plain |corr|."""
    from repro.data.dataset_search import _corr_scores
    join = jnp.array([[31.0, 2000.0, 10.0]])
    zero, one = jnp.zeros_like(join), jnp.ones_like(join)
    prod = jnp.array([[1.0, 0.9, 1.0]])           # corr 1, 0.9, 1
    score = np.asarray(_corr_scores(join, zero, zero, one, one, prod,
                                    jnp.float32(30)))[0]
    np.testing.assert_allclose(score[:2], [31 / 61, 0.9 * 2000 / 2030],
                               rtol=1e-6)
    assert score[2] == -1.0                       # fails min_join
    assert score[1] > score[0]
    plain = np.asarray(_corr_scores(join, zero, zero, one, one, prod,
                                    jnp.float32(0)))[0]
    np.testing.assert_allclose(plain, [1.0, 0.9, 1.0], rtol=1e-6)


def test_sparsevec_sum_duplicates_option():
    v = SparseVec.from_pairs([4, 1, 4, 2], [1.0, 2.0, 3.0, 4.0], 10,
                             sum_duplicates=True)
    assert list(v.indices) == [1, 2, 4]
    assert list(v.values) == [2.0, 4.0, 4.0]
    with pytest.raises(ValueError):
        SparseVec.from_pairs([4, 1, 4], [1.0, 2.0, 3.0], 10)


# ---------------------------------------------------------------------------
# serving front-end
# ---------------------------------------------------------------------------
def test_sketch_search_service():
    rng = np.random.default_rng(41)
    svc = SketchSearchService(m=512, seed=3)
    keys = np.arange(500)
    signal = rng.normal(size=500)
    svc.ingest_many([
        ("a_corr", keys, signal + 0.1 * rng.normal(size=500)),
        ("b_noise", keys, rng.normal(size=500)),
    ])
    with pytest.raises(ValueError):
        svc.ingest("a_corr", keys, signal)
    res = svc.search(keys, signal, top_k=2, min_join=20)
    assert res and res[0].name == "a_corr"
    d = svc.describe()
    assert d["tables"] == 2.0 and d["queries_served"] == 1.0
    assert svc.stats.last_query_ms > 0
