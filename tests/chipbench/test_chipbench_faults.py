"""A run with the timed path broken underneath comes out not correct: once
for each fault the cells can have, and for the control (the reference in
the program's place, computed in bfloat16)."""
import pytest

import chipbench_tiny
from chipbench import run as bench_run
from repro.serve import SketchSearchService


class HalfBatch(SketchSearchService):
    """Leaves every second request out of the batch: it gets nothing."""
    seen = 0

    def search_batch(self, queries, **kw):
        out = super().search_batch(queries, **kw)
        for i in range(len(out)):
            if (self.seen + i) % 2:
                out[i] = []
        self.seen += len(out)
        return out


class AlteredJoin(SketchSearchService):
    """Every served join size altered where it is produced."""

    def search_batch(self, queries, **kw):
        out = super().search_batch(queries, **kw)
        for res in out:
            for r in res:
                r.join_size *= 1.5
        return out


class AlteredCorr(SketchSearchService):
    """Every served correlation altered where it is produced."""

    def search_batch(self, queries, **kw):
        out = super().search_batch(queries, **kw)
        for res in out:
            for r in res:
                r.corr = -r.corr
        return out


class IngestUnchanged(SketchSearchService):
    """Acknowledges ingest batches and leaves the lake as it was."""

    def ingest_many_sharded(self, tables, **kw):
        if any(name.startswith("f") or name.startswith("w")
               for name, _, _ in tables):
            return None
        return super().ingest_many_sharded(tables, **kw)


def _run(name, **kw):
    cell = chipbench_tiny.tiny_cell(name)
    return bench_run.run(cell, 2 ** 31 + 5, 2.0, False, require_chip=False,
                         cache=False, log=lambda msg: None, **kw)


@pytest.mark.parametrize("name,fault", [
    ("w1_query_open", HalfBatch),
    ("w1_query_open", AlteredJoin),
    ("wdi_query_open", AlteredCorr),
    ("w1_ingest", IngestUnchanged),
])
def test_broken_timed_path_is_not_correct(name, fault):
    assert _run(name, service_cls=fault)["correct"] is False


@pytest.mark.parametrize("name", ["w1_query_open", "w1_ingest"])
def test_control_is_not_correct(name):
    res = _run(name, control=True)
    assert res["correct"] is False
    over = [k for k, v in res["checks"].items() if v["value"] > v["limit"]]
    assert over
    # the program's own verdict on the same run stands beside it
    assert res["program"]["correct"] is True
    assert list(res)[-2:] == ["program", "checks"]
