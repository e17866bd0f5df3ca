"""The device query path copies only the candidates to the host.

``query.fetch`` moves the candidates' scores, indices, join and sum
estimates, ``Q * k * 16`` bytes a call whatever the corpus size, and says
so in its ``bytes`` attribute and in ``query.fetch_bytes_total``.  The
join and sum estimates gathered on the device (``ops.take_candidates``)
are the full ``[Q, P]`` planes indexed at the candidates, bit for bit, on
the plain path, for tenants and when the corpus holds fewer tables than
``CANDIDATES``.
"""
import numpy as np
import pytest

from repro import obs
from repro.data import DatasetSearchIndex
from repro.data.dataset_search import CANDIDATE_PLANES, CANDIDATES
from repro.kernels import ops


@pytest.fixture(autouse=True)
def _clean_obs_state():
    was = obs.enabled()
    obs.disable()
    obs.reset_all()
    yield
    if was:
        obs.enable()
    else:
        obs.disable()
    obs.reset_all()


def _tables(rng, n, prefix="t"):
    return [(f"{prefix}{i}", rng.choice(2048, size=r, replace=False),
             rng.normal(50.0, 5.0, size=r))
            for i, r in enumerate(rng.integers(150, 400, size=n))]


def _queries(rng, q):
    return [(k, v) for _, k, v in _tables(rng, q, "q")]


def _index(family, tables, **kw):
    index = DatasetSearchIndex(m=32, seed=4, family=family,
                               keep_host_oracle=False, **kw)
    index.add_tables_sharded(tables, shards=1)
    return index


def _fetches(family):
    spans = [e["args"]["bytes"] for e in obs.events()
             if e["name"] == "query.fetch"]
    c = obs.describe_metrics()["metrics"].get("query.fetch_bytes_total")
    series = (c or {}).get("series", [])
    assert [s["labels"] for s in series] == [{"family": family}]
    return spans, series[0]["value"]


@pytest.mark.parametrize("family", ["icws", "jl"])
def test_fetch_bytes_do_not_depend_on_the_corpus(family):
    rng = np.random.default_rng(16)
    queries = _queries(rng, 3)
    small = _index(family, _tables(rng, 40))
    large = _index(family, _tables(rng, 120))
    want = len(queries) * CANDIDATES * 16
    for index in (small, large):
        obs.reset_all()
        obs.enable()
        index.query_batch(queries, top_k=5, min_join=1.0)
        obs.disable()
        spans, total = _fetches(family)
        assert spans == [want]
        assert total == want


def test_fetch_bytes_shrink_with_a_corpus_below_the_candidates():
    rng = np.random.default_rng(17)
    queries = _queries(rng, 2)
    index = _index("icws", _tables(rng, 12))
    obs.enable()
    index.query_batch(queries, top_k=5, min_join=1.0)
    index.query(*queries[0], top_k=5, min_join=1.0)
    spans, total = _fetches("icws")
    assert spans == [2 * 12 * 16, 1 * 12 * 16]
    assert total == sum(spans)


def test_with_obs_off_fetch_records_nothing():
    rng = np.random.default_rng(18)
    index = _index("icws", _tables(rng, 8))
    index.query_batch(_queries(rng, 2), top_k=3, min_join=1.0)
    assert obs.events() == []
    assert "query.fetch_bytes_total" not in obs.describe_metrics()[
        "metrics"]


def _spy(monkeypatch):
    """Record every ``ops.take_candidates`` call's inputs and outputs."""
    seen = []
    real = ops.take_candidates

    def spy(est, idx, *, planes):
        out = real(est, idx, planes=planes)
        seen.append((np.asarray(est), np.asarray(idx), planes,
                     [np.asarray(o) for o in out]))
        return out

    monkeypatch.setattr(ops, "take_candidates", spy)
    return seen


def _tenant_index(rng, fragmented):
    """Two tenants of 40 tables: appended in alternating chunks of 8
    (fragmented, gather path) or one after the other (contiguous, slice
    path)."""
    index = DatasetSearchIndex(m=32, seed=4, keep_host_oracle=False)
    a, b = _tables(rng, 40, "a"), _tables(rng, 40, "b")
    step = 8 if fragmented else 40
    for lo in range(0, 40, step):
        index.add_tables_sharded(a[lo:lo + step], shards=1, tenant="a")
        index.add_tables_sharded(b[lo:lo + step], shards=1, tenant="b")
    assert (len(index.store.tenant_ranges("a")) > 1) == fragmented
    return index


@pytest.mark.parametrize("case, tables, tenant", [
    ("plain", 48, None),
    ("fragmented tenant", 40, "a"),
    ("contiguous tenant", 40, "b"),
    ("fewer tables than candidates", 12, None),
])
def test_gathered_candidates_equal_the_full_planes_at_idx(
        monkeypatch, case, tables, tenant):
    rng = np.random.default_rng(19)
    if tenant is None:
        index = _index("icws", _tables(rng, tables))
    else:
        index = _tenant_index(rng, fragmented=case.startswith("frag"))
    queries = _queries(rng, 3)
    seen = _spy(monkeypatch)
    index.query_batch(queries, top_k=5, min_join=1.0, tenant=tenant)
    index.query(*queries[1], top_k=5, min_join=1.0, tenant=tenant)
    assert len(seen) == 2
    k = min(CANDIDATES, tables)
    for (est, idx, planes, out), q in zip(seen, (len(queries), 1)):
        assert est.shape == (6, q, tables)
        assert idx.shape == (q, k)
        assert planes == CANDIDATE_PLANES
        for g, got in zip(planes, out):
            want = est[g][np.arange(q)[:, None], idx]
            assert got.dtype == want.dtype == np.float32
            assert np.array_equal(got.view(np.uint32),
                                  want.view(np.uint32)), (case, g)
