"""The benchmark's exact reference and its comparison, on tables small
enough to work out by hand."""
import math

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (puts the repository on the path)
from chipbench import check, lakes, reference


def test_join_stats_match_hand_computed_tables():
    # query: key 1 once, key 2 twice, key 3 once
    q = (np.array([1, 2, 2, 3]), np.array([1.0, 2.0, 4.0, 5.0]))
    # table: key 2 once, key 3 twice, key 4 once
    t = (np.array([2, 3, 3, 4]), np.array([10.0, 1.0, 2.0, 7.0]))
    s = reference.join_stats(q, [t])
    # joined row pairs: key 2: 2 * 1, key 3: 1 * 2
    assert s.join[0] == 4.0
    # B's values over the pairs: key 2: 2 * 10, key 3: 1 * (1 + 2)
    assert s.sum_b[0] == 23.0
    # two shared keys, per-key sums (6, 5) vs (10, 3): both fall
    assert s.corr[0] == pytest.approx(1.0)
    assert s.shared[0] == 2
    assert s.norm_a == pytest.approx(math.sqrt(1 + 4 + 1))
    assert s.norm_b[0] == pytest.approx(math.sqrt(1 + 4 + 1))
    assert s.vnorm_b[0] == pytest.approx(math.sqrt(100 + 9 + 49))


def test_correlation_is_pearson_over_shared_keys():
    rng = np.random.default_rng(0)
    keys = np.arange(50)
    a = rng.normal(size=50)
    b = 3.0 * a + rng.normal(size=50)
    s = reference.join_stats((keys, a), [(keys[10:], b[10:]),
                                         (np.array([999]), np.array([1.0]))])
    assert s.corr[0] == pytest.approx(np.corrcoef(a[10:], b[10:])[0, 1])
    assert s.join[1] == 0 and s.corr[1] == 0


def test_bf16_control_accumulates_in_bf16():
    keys = np.arange(1000)
    ones = np.ones(1000)
    exact = reference.join_stats((keys, ones), [(keys, ones)])
    ctl = reference.join_stats((keys, ones), [(keys, ones)],
                               dtype=reference.BF16)
    assert exact.join[0] == 1000.0
    assert ctl.join[0] == 256.0          # a bf16 running sum stalls at 256


def test_lake_index_ranks_the_correlated_table_first():
    rng = np.random.default_rng(1)
    keys = np.arange(200)
    a = rng.normal(size=200)
    names = ["noise", "twin", "far"]
    tabs = lakes.from_lists(names, [keys, keys, keys + 1000],
                            [rng.normal(size=200), 2 * a + 0.01, a])
    idx = reference.LakeIndex(tabs)
    top = idx.rank((keys, a), top_k=2, min_join=30)
    assert [t[0] for t in top] == ["twin", "noise"]
    assert top[0][1] == 200.0 and top[0][3] == pytest.approx(1.0)


def _lake():
    rng = np.random.default_rng(2)
    keys = np.arange(300)
    a = rng.normal(size=300)
    tabs = lakes.from_lists(["p0", "p1", "bg"],
                            [keys, np.tile(keys, 2), keys[:100]],
                            [a + 0.1 * rng.normal(size=300),
                             np.tile(-a, 2), rng.normal(size=100)])
    return (keys, a), tabs


def _exact_answer(query, tabs):
    out = []
    for i in range(len(tabs)):
        name, k, v = tabs.table(i)
        s = reference.join_stats(query, [(k, v)])
        out.append((name, s.join[0], s.sum_b[0], s.corr[0]))
    return out


def _lookup(tabs):
    def lookup(name):
        try:
            return tabs.table(tabs.index(name))[1:]
        except KeyError:
            return None
    return lookup


@pytest.mark.parametrize("fault", ["none", "join", "sum", "corr_sign",
                                   "missing", "unknown"])
def test_comparison_fails_on_a_corrupted_answer(fault):
    query, tabs = _lake()
    answer = _exact_answer(query, tabs)
    if fault == "join":
        answer = [(n, 1.5 * j, s, c) for n, j, s, c in answer]
    elif fault == "sum":
        answer = [(n, j, 0.5 * s, c) for n, j, s, c in answer]
    elif fault == "corr_sign":
        answer = [(n, j, s, -c) for n, j, s, c in answer]
    elif fault == "missing":
        answer = answer[1:]
    elif fault == "unknown":
        answer = answer + [("nowhere", 1.0, 1.0, 0.0)]
    limits = {"planted_missed": 0.0, "join_err": 0.01, "sum_err": 0.01,
              "corr_err": 0.01}
    readings = check.compare([query], [answer], [["p0", "p1"]],
                             _lookup(tabs))
    assert check.verdict(readings, limits, failed=0,
                         planted_checked=2) == (fault == "none")
    if fault == "none":
        assert all(v < 1e-12 for v in readings.values())


def test_a_failed_request_fails_the_run():
    query, tabs = _lake()
    readings = check.compare([query], [_exact_answer(query, tabs)],
                             [["p0"]], _lookup(tabs))
    limits = dict.fromkeys(check.NUMBERS, 1.0)
    assert check.verdict(readings, limits, failed=0, planted_checked=1)
    assert not check.verdict(readings, limits, failed=1, planted_checked=1)
    assert not check.verdict(readings, limits, failed=0, planted_checked=0)
