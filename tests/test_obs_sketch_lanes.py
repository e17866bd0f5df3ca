"""The query sketch launch's lanes on the ``query.dispatch`` span and in
``query.sketch_lanes_total``: every family reports the launch's ``rows``
(3Q), padded non-zero ``width`` (the width the batch padding builds) and
real non-zeros ``nnz``, they add up, and with observability off nothing is
computed or recorded."""
import numpy as np
import pytest

from repro import obs
from repro.data.families import FAMILY_NAMES
from repro.core import SparseVec
from repro.data.ingest import (pad_linear_batch, pad_sparse_batch,
                               padded_width)
from repro.serve import SketchSearchService

MICRO_BATCH = 4


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


def _tables(rng, n, rows):
    return [(f"t{i}", rng.choice(4096, size=r, replace=False),
             rng.normal(100.0, 10.0, size=r))
            for i, r in enumerate(rng.integers(rows // 2, rows, size=n))]


def _service(family, rng):
    svc = SketchSearchService(m=16, family=family, packed=True,
                              keep_host_oracle=False)
    svc.ingest_many_sharded(_tables(rng, 8, 300), shards=1)
    return svc


def _lanes(family):
    c = obs.describe_metrics()["metrics"].get("query.sketch_lanes_total")
    out = {}
    for s in (c or {}).get("series", []):
        assert s["labels"]["family"] == family
        out[s["labels"]["kind"]] = s["value"]
    return out


@pytest.mark.parametrize("family", FAMILY_NAMES)
def test_dispatch_span_carries_the_launch_lanes_and_they_add_up(family):
    rng = np.random.default_rng(15)
    svc = _service(family, rng)
    queries = [(k, v) for _, k, v in _tables(rng, 6, 700)]
    obs.enable()
    svc.search_batch(queries, top_k=3, min_join=1.0,
                     micro_batch=MICRO_BATCH)
    spans = [e["args"] for e in obs.events()
             if e["name"] == "query.dispatch"]
    assert len(spans) == 2                  # 4 queries, then 2 and 2 empty
    served = [sum(v.nnz for v in svc.index.served_vectors(k, v)[0])
              for k, v in queries]
    for i, a in enumerate(spans):
        assert a["rows"] == 3 * MICRO_BATCH
        assert 0 < a["nnz"] <= a["rows"] * a["width"]
        assert a["nnz"] == sum(served[4 * i:4 * i + 4])
        assert a["width"] % 256 == 0
        assert a["width"] >= max(
            v.nnz for k, val in queries[4 * i:4 * i + 4]
            for v in svc.index.served_vectors(k, val)[0])
    lanes = _lanes(family)
    assert set(lanes) == {"real", "pad"}
    assert lanes["real"] == sum(a["nnz"] for a in spans)
    assert lanes["real"] + lanes["pad"] == sum(a["rows"] * a["width"]
                                               for a in spans)


@pytest.mark.parametrize("pad", [pad_sparse_batch, pad_linear_batch])
def test_the_width_is_the_one_the_batch_padding_builds(pad):
    rng = np.random.default_rng(17)
    vecs = [SparseVec(np.sort(rng.choice(4096, size=n, replace=False)),
                      np.ones(n), 4096) for n in (5, 300, 0, 257)]
    nnz = np.array([v.nnz for v in vecs], np.int64)
    assert pad(vecs)[0].shape == (len(vecs), padded_width(nnz)) == (4, 512)


@pytest.mark.parametrize("nnz, bucket, want", [
    ([], 256, 256), ([0, 0], 256, 256), ([1], 256, 256), ([256], 256, 256),
    ([257, 3], 256, 512), ([13888], 256, 14080), ([100], 64, 128)])
def test_padded_width_rounds_the_longest_up_to_a_bucket(nnz, bucket, want):
    assert padded_width(np.asarray(nnz, np.int64), bucket) == want


def test_with_obs_off_dispatch_records_nothing():
    rng = np.random.default_rng(16)
    svc = _service("jl", rng)
    svc.search_batch([(k, v) for _, k, v in _tables(rng, 2, 500)],
                     top_k=3, min_join=1.0, micro_batch=MICRO_BATCH)
    assert obs.events() == []
    assert "query.sketch_lanes_total" not in obs.describe_metrics()[
        "metrics"]
