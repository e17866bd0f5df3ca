"""Batched many-vs-many query engine.

Covers: the fused multi-field kernel vs its jnp oracle at one field pair
and at many (property-tested via hypothesis); a query's estimates alone ==
its row in a batch; batched == sequential estimates against a single-field
``CorpusStore``; and end-to-end identity of ``DatasetSearchIndex.
query_batch`` / ``SketchSearchService.search_batch`` with a loop of single
queries on both backends.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.data import CorpusStore, DatasetSearchIndex, sketch_batch
from repro.data.synthetic import sparse_pair
from repro.kernels import ops, ref
from repro.kernels.estimate import estimate_fields_pallas
from repro.serve import SketchSearchService


# one query field against one corpus field: the plain ICWS estimate
ONE_PAIR = {"qmap": (0,), "cmap": (0,)}


def _sketch_pair_batch(rng, Q, P, m, lo=0, hi=40):
    """Random one-field fingerprint/value batches ``[1, Q, m]`` /
    ``[1, P, m]`` with plenty of collisions."""
    fq = rng.integers(lo, hi, size=(1, Q, m)).astype(np.int32)
    fc = rng.integers(lo, hi, size=(1, P, m)).astype(np.int32)
    vq = rng.normal(size=(1, Q, m)).astype(np.float32)
    vc = rng.normal(size=(1, P, m)).astype(np.float32)
    return (jnp.asarray(fq), jnp.asarray(vq), jnp.asarray(fc), jnp.asarray(vc))


# ---------------------------------------------------------------------------
# fields kernel vs ref oracle (property-tested)
# ---------------------------------------------------------------------------
@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(q=st.integers(1, 12), p=st.integers(1, 18),
       m=st.integers(1, 280), seed=st.integers(0, 2 ** 31 - 1))
def test_many_vs_many_kernel_matches_ref(q, p, m, seed):
    rng = np.random.default_rng(seed)
    fq, vq, fc, vc = _sketch_pair_batch(rng, q, p, m)
    cnt_k, sw_k = estimate_fields_pallas(fq, vq, fc, vc, interpret=True,
                                         **ONE_PAIR)
    cnt_r, sw_r = ref.estimate_fields_ref(fq, vq, fc, vc, **ONE_PAIR)
    assert cnt_k.shape == (1, q, p)
    np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_r))
    # adversarial random values make the collision terms span many orders of
    # magnitude, so normalize by the result scale (the kernel reduces m in
    # bm-sized blocks; the oracle reduces the whole axis at once)
    sw_r = np.asarray(sw_r)
    scale = max(1.0, float(np.max(np.abs(sw_r))))
    np.testing.assert_allclose(np.asarray(sw_k), sw_r, rtol=1e-4,
                               atol=1e-4 * scale)


@pytest.mark.slow
@settings(max_examples=8, deadline=None)
@given(data=st.data())
def test_fields_kernel_matches_ref(data):
    seed = data.draw(st.integers(0, 2 ** 31 - 1))
    rng = np.random.default_rng(seed)
    F = data.draw(st.integers(1, 3))
    C = data.draw(st.integers(1, 3))
    G = data.draw(st.integers(1, 7))
    qmap = tuple(data.draw(st.integers(0, F - 1)) for _ in range(G))
    cmap = tuple(data.draw(st.integers(0, C - 1)) for _ in range(G))
    Q, P, m = (data.draw(st.integers(1, 10)), data.draw(st.integers(1, 14)),
               data.draw(st.integers(1, 200)))
    fq = jnp.asarray(rng.integers(0, 30, size=(F, Q, m)).astype(np.int32))
    vq = jnp.asarray(rng.normal(size=(F, Q, m)).astype(np.float32))
    fc = jnp.asarray(rng.integers(0, 30, size=(C, P, m)).astype(np.int32))
    vc = jnp.asarray(rng.normal(size=(C, P, m)).astype(np.float32))
    cnt_k, sw_k = estimate_fields_pallas(fq, vq, fc, vc, qmap=qmap, cmap=cmap,
                                         interpret=True)
    cnt_r, sw_r = ref.estimate_fields_ref(fq, vq, fc, vc, qmap=qmap, cmap=cmap)
    assert cnt_k.shape == (G, Q, P)
    np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_r))
    sw_r = np.asarray(sw_r)
    scale = max(1.0, float(np.max(np.abs(sw_r))))
    np.testing.assert_allclose(np.asarray(sw_k), sw_r, rtol=1e-4,
                               atol=1e-4 * scale)


def _fields_inputs(seed, Q, P, m, bf16, F=3, C=3):
    """Field-stacked sketches with collisions, pad sentinels (-1, -2) among
    the fingerprints and zeros among the values of both sides."""
    rng = np.random.default_rng(seed)
    fq = rng.integers(-2, 30, size=(F, Q, m)).astype(np.int32)
    fc = rng.integers(-2, 30, size=(C, P, m)).astype(np.int32)
    vq = rng.normal(size=(F, Q, m)).astype(np.float32)
    vc = rng.normal(size=(C, P, m)).astype(np.float32)
    vq[rng.random(vq.shape) < 0.2] = 0.0
    vc[rng.random(vc.shape) < 0.2] = 0.0
    vc = jnp.asarray(vc, jnp.bfloat16 if bf16 else jnp.float32)
    return jnp.asarray(fq), jnp.asarray(vq), jnp.asarray(fc), vc


_ONE_PAIR = ((2,), (1,))


@pytest.mark.parametrize("Q,P,m,bf16,maps", [
    (1, 1, 200, False, "fields"),
    (1, 130, 256, True, "fields"),
    (8, 130, 256, True, "fields"),
    (8, 300, 200, False, "one"),
    (9, 300, 200, True, "one"),
    (9, 1, 256, False, "one"),
    (9, 130, 300, True, "fields"),     # three 128-slot blocks
    (16, 300, 256, False, "fields"),
    (16, 130, 200, True, "fields"),
])
def test_fields_kernel_layout_cases_match_ref(Q, P, m, bf16, maps):
    """The fields kernel against its oracle across the padding paths: Q
    off the query block, P off the row block, m off the lane width or over
    one slot block, packed (bf16) corpus values, zero values and negative
    fingerprints on both sides, the §1.3 maps and a single pair."""
    from repro.data.dataset_search import CFIELD, QFIELD
    qmap, cmap = (QFIELD, CFIELD) if maps == "fields" else _ONE_PAIR
    fq, vq, fc, vc = _fields_inputs(Q * 1000 + P + m, Q, P, m, bf16)
    cnt_k, sw_k = estimate_fields_pallas(fq, vq, fc, vc, qmap=qmap,
                                         cmap=cmap, interpret=True)
    cnt_r, sw_r = ref.estimate_fields_ref(fq, vq, fc, vc.astype(jnp.float32),
                                          qmap=qmap, cmap=cmap)
    assert cnt_k.shape == (len(qmap), Q, P)
    np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_r))
    sw_r = np.asarray(sw_r)
    scale = max(1.0, float(np.max(np.abs(sw_r))))
    np.testing.assert_allclose(np.asarray(sw_k), sw_r, rtol=1e-4,
                               atol=1e-4 * scale)


@pytest.mark.parametrize("maps", ["fields", "one"])
def test_fields_kernel_query_row_independent_of_batch(maps):
    """A query's row alone (Q = 1) is bitwise its row in a batch: a packed
    16-query batch over the §1.3 maps, and every row of an f32 6-query
    batch at one field pair."""
    from repro.data.dataset_search import CFIELD, QFIELD
    if maps == "fields":
        pairs, rows = {"qmap": QFIELD, "cmap": CFIELD}, (0, 9, 15)
        fq, vq, fc, vc = _fields_inputs(5, 16, 300, 256, True)
    else:
        pairs, rows = ONE_PAIR, range(6)
        fq, vq, fc, vc = _sketch_pair_batch(np.random.default_rng(11),
                                            6, 13, 260)
    batch = estimate_fields_pallas(fq, vq, fc, vc, interpret=True, **pairs)
    for i in rows:
        alone = estimate_fields_pallas(fq[:, i:i + 1], vq[:, i:i + 1], fc, vc,
                                       interpret=True, **pairs)
        for a, b in zip(alone, batch):
            np.testing.assert_array_equal(np.asarray(a)[:, 0],
                                          np.asarray(b)[:, i])


def test_fields_kernel_table_column_independent_of_slice():
    """A table's column from a slice of the corpus rows is bitwise its
    column in the whole corpus, wherever the slice starts."""
    from repro.data.dataset_search import CFIELD, QFIELD
    fq, vq, fc, vc = _fields_inputs(7, 9, 1100, 200, False)
    whole = estimate_fields_pallas(fq, vq, fc, vc, qmap=QFIELD, cmap=CFIELD,
                                   interpret=True)
    lo, hi = 600, 1050      # other row blocks and lanes than in the whole
    part = estimate_fields_pallas(fq, vq, fc[:, lo:hi], vc[:, lo:hi],
                                  qmap=QFIELD, cmap=CFIELD, interpret=True)
    for a, b in zip(part, whole):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(b)[:, :, lo:hi])


def test_many_vs_many_empty_query_guard():
    """All-empty query rows (fp == -1) collide with nothing; padding rows of
    a ragged batch behave like empty queries."""
    Q, P, m = 3, 5, 128
    fq = jnp.full((1, Q, m), -1, jnp.int32)
    vq = jnp.zeros((1, Q, m))
    fc = jnp.full((1, P, m), -1, jnp.int32)
    vc = jnp.zeros((1, P, m))
    cnt, sw = estimate_fields_pallas(fq, vq, fc, vc, interpret=True,
                                     **ONE_PAIR)
    assert np.all(np.asarray(cnt) == 0.0) and np.all(np.asarray(sw) == 0.0)


def test_many_vs_many_matches_ref_on_real_sketches():
    """On actual ICWS sketch values (the serving regime), kernel and oracle
    agree to 1e-5 relative -- the acceptance bar."""
    rng = np.random.default_rng(29)
    vecs = [sparse_pair(rng, n=500, nnz=120, overlap=0.3)[0] for _ in range(9)]
    queries = [sparse_pair(rng, n=500, nnz=120, overlap=0.3)[0]
               for _ in range(5)]
    fq, vq, _, _ = sketch_batch(queries, m=256, seed=4)
    fc, vc, _, _ = sketch_batch(vecs, m=256, seed=4)
    args = tuple(x[None] for x in (fq, vq, fc, vc))
    cnt_k, sw_k = estimate_fields_pallas(*args, interpret=True, **ONE_PAIR)
    cnt_r, sw_r = ref.estimate_fields_ref(*args, **ONE_PAIR)
    np.testing.assert_array_equal(np.asarray(cnt_k), np.asarray(cnt_r))
    sw_k, sw_r = np.asarray(sw_k, np.float64), np.asarray(sw_r, np.float64)
    scale = np.maximum(np.maximum(np.abs(sw_k), np.abs(sw_r)), 1e-12)
    assert float(np.max(np.abs(sw_k - sw_r) / scale)) < 1e-5


# ---------------------------------------------------------------------------
# batched estimation against a single-field store
# ---------------------------------------------------------------------------
def test_corpus_estimate_batch_matches_sequential():
    rng = np.random.default_rng(19)
    vecs = [sparse_pair(rng, n=500, nnz=120, overlap=0.3)[0] for _ in range(9)]
    queries = [sparse_pair(rng, n=500, nnz=120, overlap=0.3)[0]
               for _ in range(5)]
    store = CorpusStore(m=128, fields=1)
    store.append(*sketch_batch(vecs, m=128, seed=3))

    def estimate(qs):
        fq, vq, nq, _ = sketch_batch(qs, m=128, seed=3)
        return np.asarray(ops.icws_estimate_fields(
            fq[None], vq[None], nq[None], *store.buffers()[:3],
            **ONE_PAIR)[0, :, :len(store)])

    batched = estimate(queries)
    assert batched.shape == (5, 9)
    for qi, q in enumerate(queries):
        np.testing.assert_array_equal(batched[qi], estimate([q])[0])


# ---------------------------------------------------------------------------
# end-to-end: query_batch == loop of query on both backends
# ---------------------------------------------------------------------------
def _build_index(rng, m=512):
    idx = DatasetSearchIndex(m=m, seed=1)
    keys = np.arange(600)
    signal = rng.normal(size=600)
    idx.add_table("corr", keys, signal + 0.2 * rng.normal(size=600))
    idx.add_table("noise", keys, rng.normal(size=600))
    idx.add_table("disjoint", np.arange(9000, 9600), rng.normal(size=600))
    idx.add_table("half", np.arange(300, 900), rng.normal(size=600))
    queries = [(keys, signal + 0.1 * rng.normal(size=600)),
               (np.arange(100, 700), rng.normal(size=600)),
               (np.arange(50), rng.normal(size=50))]
    return idx, queries


@pytest.mark.parametrize("backend", ["device", "host"])
def test_query_batch_identical_to_query_loop(backend):
    rng = np.random.default_rng(5)
    idx, queries = _build_index(rng)
    batch = idx.query_batch(queries, top_k=4, min_join=20, backend=backend)
    seq = [idx.query(k, v, top_k=4, min_join=20, backend=backend)
           for k, v in queries]
    assert batch == seq          # SearchResult dataclass equality: all stats


@pytest.mark.parametrize("n,k", [(11, 6), (300, 128), (5, 5)])
def test_top_k_breaks_ties_by_ascending_index(n, k):
    """``ops.top_k`` ranks by (score descending, index ascending), and a
    row's top-k does not depend on the batch it is ranked in -- the
    property batched == sequential serving rests on, whatever order the
    backend's own ``top_k`` gives equal scores."""
    rng = np.random.default_rng(n)
    score = rng.integers(-1, 3, size=(16, n)).astype(np.float32)
    v, i = (np.asarray(x) for x in ops.top_k(jnp.asarray(score), k))
    for q in range(16):
        want = np.lexsort((np.arange(n), -score[q]))[:k]
        assert np.array_equal(i[q], want)
        assert np.array_equal(v[q], score[q, want])
        _, i1 = ops.top_k(jnp.asarray(score[q:q + 1]), k)
        assert np.array_equal(np.asarray(i1)[0], want)


def test_query_batch_empty_inputs():
    idx = DatasetSearchIndex(m=64, seed=0)
    assert idx.query_batch([]) == []
    assert idx.query_batch([(np.arange(3), np.ones(3))]) == [[]]  # no tables


def test_search_batch_identical_to_search_loop_and_stats():
    rng = np.random.default_rng(7)
    svc = SketchSearchService(m=256, seed=2)
    keys = np.arange(400)
    signal = rng.normal(size=400)
    svc.ingest("a_corr", keys, signal + 0.1 * rng.normal(size=400))
    svc.ingest("b_noise", keys, rng.normal(size=400))
    queries = [(keys, signal + 0.05 * rng.normal(size=400)) for _ in range(5)]
    # micro_batch=4 forces a padded tail batch (5 = 4 + 1 padded to 4)
    batch = svc.search_batch(queries, top_k=2, min_join=10, micro_batch=4)
    seq = [svc.search(k, v, top_k=2, min_join=10) for k, v in queries]
    assert batch == seq
    assert svc.stats.batches_served == 2
    assert svc.stats.batch_queries_served == 5
    assert svc.stats.last_batch_ms > 0
    d = svc.describe()
    assert d["batch_queries_served"] == 5.0
    assert d["mean_batched_query_ms"] > 0
    with pytest.raises(ValueError):
        svc.search_batch(queries, micro_batch=0)


def test_search_batch_host_backend_matches_loop():
    rng = np.random.default_rng(13)
    svc = SketchSearchService(m=256, seed=2)
    keys = np.arange(300)
    signal = rng.normal(size=300)
    svc.ingest("t0", keys, signal)
    svc.ingest("t1", keys, rng.normal(size=300))
    queries = [(keys, signal), (np.arange(100, 400), rng.normal(size=300))]
    batch = svc.search_batch(queries, top_k=2, min_join=5, backend="host",
                             micro_batch=8)
    seq = [svc.search(k, v, top_k=2, min_join=5, backend="host")
           for k, v in queries]
    assert batch == seq
