"""CPU rehearsal of ``chip_smoke.py``: its phases and checks on a tiny lake.

The script itself refuses to run without a TPU (there is no CPU fallback
in ``main()``); these tests import its phase functions and run them here,
with the Pallas kernels interpreted, so a broken phase or check shows up
before any chip time is spent.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import chip_smoke as cs  # noqa: E402
from repro.serve import SketchSearchService  # noqa: E402


def test_smoke_phases_pass_on_a_tiny_lake():
    lake = cs.make_lake(3, 96, 2)
    assert len(lake) == 96 and len(lake.queries) == 2
    svc = SketchSearchService(m=cs.M, packed=True, keep_host_oracle=False)
    where = cs.ingest(svc, lake)
    assert svc.index.store.size == len(lake) == len(where)
    results = cs.serve(svc, lake)
    assert cs.check_recall(lake, results) == 0
    assert cs.check_join_error(lake, where, results) <= cs.JOIN_TOL
    assert cs.check_parity(svc, lake, results) <= cs.PARITY_TOL
    assert cs.check_batched_equals_sequential(svc, lake)


@pytest.mark.parametrize("family", cs.OTHER_FAMILIES)
def test_smoke_serves_every_other_family(family):
    n, recall, finite = cs.serve_family(family, cs.make_lake(4, 48, 1))
    assert n == 48 and finite and 0.0 <= recall <= 1.0


def test_exact_join_counts_row_pairs():
    join, na, nb = cs.exact_join([1, 1, 2, 5], [1, 2, 2, 2, 7])
    assert join == 2 * 1 + 1 * 3
    assert na == pytest.approx((4 + 1 + 1) ** 0.5)
    assert nb == pytest.approx((1 + 9 + 1) ** 0.5)


def test_main_refuses_to_run_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""
