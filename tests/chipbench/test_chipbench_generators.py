"""The benchmark's traffic and lake generators are fixed by ``--seed``."""
import numpy as np
import pytest

import chipbench_tiny
from chipbench import mix

SEED = 2 ** 31 + 17          # seeds may pass 32 signed bits


def _build(name, seed):
    cell = chipbench_tiny.tiny_cell(name)
    return mix.build(cell.config, cell.traffic, seed, 2.0)


def _same(a, b):
    assert a.lake.names == b.lake.names
    np.testing.assert_array_equal(a.lake.keys, b.lake.keys)
    np.testing.assert_array_equal(a.lake.values, b.lake.values)
    assert a.planted == b.planted
    assert len(a.queries) == len(b.queries)
    for (ka, va), (kb, vb) in zip(a.queries, b.queries):
        np.testing.assert_array_equal(ka, kb)
        np.testing.assert_array_equal(va, vb)
    if a.arrivals is not None:
        np.testing.assert_array_equal(a.arrivals, b.arrivals)
    if a.pool is not None:
        np.testing.assert_array_equal(a.pool.keys, b.pool.keys)
    assert a.warm_nnz == b.warm_nnz


@pytest.mark.parametrize("name", ["w1_query_open", "wdi_query_open",
                                  "w1_ingest"])
def test_same_seed_same_inputs(name):
    _same(_build(name, SEED), _build(name, SEED))


@pytest.mark.parametrize("name", ["w1_query_open", "wdi_query_open",
                                  "w1_ingest"])
def test_seeds_share_the_work_in_another_order(name):
    """Two seeds draw other data, but the same multiset of table and query
    sizes, and of arrival gaps: every seed does the same work."""
    a, b = _build(name, SEED), _build(name, SEED + 1)
    assert not np.array_equal(a.lake.keys, b.lake.keys)
    np.testing.assert_array_equal(np.sort(a.lake.rows()),
                                  np.sort(b.lake.rows()))
    qa = sorted(len(k) for k, _ in a.queries)
    qb = sorted(len(k) for k, _ in b.queries)
    assert qa == qb
    assert sum(map(len, a.planted)) == sum(map(len, b.planted))
    if a.arrivals is not None:
        gaps = [np.sort(np.diff(np.r_[0.0, x.arrivals, 2.0]))
                for x in (a, b)]
        np.testing.assert_allclose(*gaps)
        assert 0 <= a.arrivals[0] and a.arrivals[-1] < 2.0
    if a.pool is not None:
        np.testing.assert_array_equal(np.sort(a.pool.rows()),
                                      np.sort(b.pool.rows()))


def test_open_loop_requests_are_distinct_and_planted_tables_are_in_the_lake():
    inp = _build("w1_query_open", SEED)
    firsts = {(int(k[0]), k.size) for k, _ in inp.queries}
    assert len(firsts) == len(inp.queries)
    planted = [n for names in inp.planted for n in names]
    assert planted and all(inp.lookup(n) is not None for n in planted)


def test_stream_pool_holds_each_check_querys_twins_in_its_batch():
    cell = chipbench_tiny.tiny_cell("w1_ingest")
    inp = mix.build(cell.config, cell.traffic, SEED, 2.0)
    B = cell.serving["ingest_batch"]
    for b, names in enumerate(inp.planted):
        where = [inp.pool.index(n) for n in names]
        assert all(b * B <= i < (b + 1) * B for i in where)
