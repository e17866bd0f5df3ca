"""``repro.analysis`` rule tests: seeded-bad fixtures + repo self-check.

Tier-1 and jax-free: the analysis package is pure stdlib, so every test
here runs in milliseconds with nothing installed.  Each fixture test
builds a minimal synthetic checkout under ``tmp_path``, seeds exactly one
violation, and asserts the expected rule fires at the expected file:line
-- and that the rule's group raises nothing else, so fixtures prove
precision, not just recall.  The self-check runs the full pass over this
actual repo and requires it clean (the same gate CI's lint-invariants job
enforces with ``--strict``).
"""
import os
import pathlib
import sys
import textwrap
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(__file__)),
                                "src"))

from repro.analysis import Config, load_baseline, run  # noqa: E402
from repro.analysis.config import BaselineError, parse_baseline  # noqa: E402
from repro.analysis.engine import METRICS_MD, STREAMS_MD  # noqa: E402

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

# Minimal mirrored registries used as the clean base of fixture checkouts.
DEVICE_COMMON = """
ICWS_R1_STREAM = 1
CS_SIGN_STREAM = 22


def salt_for(seed, stream, t):
    return seed ^ stream ^ t
"""
HOST_U32 = """
ICWS_R1_STREAM = 1
CS_SIGN_STREAM = 22
"""


def build_repo(tmp_path, files):
    """Write ``{repo-relative path: source}`` and return a checkout root."""
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return tmp_path


def run_rules(root, prefixes, baseline=None):
    cfg = Config(root=root, rules=tuple(prefixes),
                 baseline_path=baseline if baseline is not None
                 else root / "nonexistent-baseline.toml")
    return run(cfg)


def one_finding(result, rule):
    assert [f.rule for f in result.findings] == [rule], result.findings
    return result.findings[0]


def test_sr001_duplicate_stream_id(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/kernels/common.py": """
            ICWS_R1_STREAM = 1
            CS_SIGN_STREAM = 1
        """,
        "src/repro/core/u32.py": """
            ICWS_R1_STREAM = 1
            CS_SIGN_STREAM = 1
        """,
    })
    result = run_rules(root, ["SR001"])
    assert len(result.findings) == 2          # one per registry side
    for f in result.findings:
        assert f.rule == "SR001"
        assert "duplicate" in f.message and "1" in f.message
    dev = [f for f in result.findings if "device" in f.message]
    assert dev and dev[0].path == "src/repro/kernels/common.py"
    assert dev[0].line == 3                   # second definition anchors it


def test_sr002_host_stream_without_device_mirror(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/kernels/common.py": DEVICE_COMMON,
        "src/repro/core/u32.py": HOST_U32 + "ORPHAN_STREAM = 7\n",
    })
    f = one_finding(run_rules(root, ["SR002"]), "SR002")
    assert f.path == "src/repro/core/u32.py"
    assert f.line == 4
    assert "ORPHAN_STREAM" in f.message and "no device mirror" in f.message


def test_sr003_device_stream_without_host_twin(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/kernels/common.py": DEVICE_COMMON + "LONELY_STREAM = 8\n",
        "src/repro/core/u32.py": HOST_U32,
    })
    f = one_finding(run_rules(root, ["SR003"]), "SR003")
    assert f.path == "src/repro/kernels/common.py"
    assert "LONELY_STREAM" in f.message and "no host twin" in f.message


def test_sr004_mirror_value_disagreement(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/kernels/common.py": DEVICE_COMMON,
        "src/repro/core/u32.py": "ICWS_R1_STREAM = 1\nCS_SIGN_STREAM = 23\n",
    })
    f = one_finding(run_rules(root, ["SR004"]), "SR004")
    assert f.path == "src/repro/core/u32.py" and f.line == 2
    assert "CS_SIGN_STREAM" in f.message
    assert "host 23" in f.message and "device 22" in f.message


def test_sr005_inline_stream_literal(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/kernels/common.py": DEVICE_COMMON,
        "src/repro/core/u32.py": HOST_U32,
        "src/repro/kernels/bad_kernel.py": """
            from .common import salt_for


            def sketch(seed, t):
                good = salt_for(seed, 0x15 - 20, t)    # folded expr: fine
                return salt_for(seed, 22, t)
        """,
    })
    f = one_finding(run_rules(root, ["SR005"]), "SR005")
    assert f.path == "src/repro/kernels/bad_kernel.py" and f.line == 7
    assert "inline stream literal 22" in f.message


def test_sr005_literal_through_local_stream_helper(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/kernels/common.py": DEVICE_COMMON,
        "src/repro/core/u32.py": HOST_U32,
        "src/repro/core/bad_host.py": """
            from . import u32
            from repro.kernels.common import salt_for


            def variates(seed, t):
                def u(stream):
                    return salt_for(seed, stream, t)

                return u(u32.ICWS_R1_STREAM) * u(2)
        """,
    })
    f = one_finding(run_rules(root, ["SR005"]), "SR005")
    assert f.path == "src/repro/core/bad_host.py" and f.line == 10
    assert "literal 2" in f.message and "u()" in f.message


def test_sr006_streams_md_missing_and_stale(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/kernels/common.py": DEVICE_COMMON,
        "src/repro/core/u32.py": HOST_U32,
    })
    f = one_finding(run_rules(root, ["SR006"]), "SR006")
    assert f.path == STREAMS_MD and "missing" in f.message

    result = run_rules(root, ["SR"])
    assert [x.rule for x in result.findings] == ["SR006"]
    (root / STREAMS_MD).write_text(result.streams_md)
    assert run_rules(root, ["SR"]).ok          # regenerated => clean sweep
    (root / STREAMS_MD).write_text("# stale\n")
    f = one_finding(run_rules(root, ["SR006"]), "SR006")
    assert "stale" in f.message


def test_cb001_direct_shard_map(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/launch/bad_mesh.py": """
            import jax


            def launch(fn, mesh, specs):
                return jax.shard_map(fn, mesh=mesh, in_specs=specs,
                                     out_specs=specs[0])
        """,
        "src/repro/compat.py": """
            import jax

            shard_map = jax.shard_map        # the one licensed spelling
        """,
    })
    f = one_finding(run_rules(root, ["CB001"]), "CB001")
    assert f.path == "src/repro/launch/bad_mesh.py" and f.line == 6
    assert "jax.shard_map" in f.message and "repro.compat" in f.message


def test_cb001_gated_import_forms(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/a.py": "from jax.experimental.shard_map import shard_map\n",
        "src/repro/b.py": "import jax.experimental.shard_map as shmap\n",
        "src/repro/c.py": "from jax.sharding import AxisType\n",
        "src/repro/d.py": "import jax\nmesh = jax.make_mesh((2,), ('x',))\n",
    })
    result = run_rules(root, ["CB"])
    got = {(f.path, f.rule) for f in result.findings}
    assert got == {("src/repro/a.py", "CB001"), ("src/repro/b.py", "CB001"),
                   ("src/repro/c.py", "CB002"), ("src/repro/d.py", "CB003")}


def test_cb004_hardcoded_interpret_true(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/kernels/bad_call.py": """
            from jax.experimental import pallas as pl


            def f(kernel, x, interpret=True):      # signature default: fine
                return pl.pallas_call(kernel, out_shape=x,
                                      interpret=True)(x)
        """,
        # test/bench code is out of scope for CB004 by design
        "tests/helper.py": "def g(call, x):\n    return call(x, interpret=True)\n",
    })
    f = one_finding(run_rules(root, ["CB004"]), "CB004")
    assert f.path == "src/repro/kernels/bad_call.py" and f.line == 7
    assert "ops._interpret()" in f.message


def test_pb001_oversized_blockspec(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/kernels/bad_budget.py": """
            from jax.experimental import pallas as pl

            LANES = 128


            def huge_pallas(x, bq=8, bp=4096):
                return pl.pallas_call(
                    lambda q_ref, o_ref: None,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((bq, bp, LANES),
                                           lambda i: (i, 0, 0))] * 2,
                    out_specs=pl.BlockSpec((bq, bp), lambda i: (i, 0)),
                    out_shape=x,
                )(x)
        """,
    })
    result = run_rules(root, ["PB"])
    f = one_finding(result, "PB001")
    assert f.path == "src/repro/kernels/bad_budget.py" and f.line == 8
    # 2 * (8*4096*128) * 4B + (8*4096) * 4B = 33685504 > 2 MiB
    assert "33685504 bytes" in f.message and "huge_pallas" in f.message
    (entry,) = result.budget_report
    assert entry["kernel"] == "huge_pallas"
    assert entry["total_block_bytes"] == 33685504
    assert not entry["within_budget"] and not entry["unresolved"]


def test_pb002_runtime_dependent_block_shape(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/kernels/bad_shape.py": """
            from jax.experimental import pallas as pl


            def dyn_pallas(x):
                S = x.shape[0]
                return pl.pallas_call(
                    lambda q_ref, o_ref: None,
                    in_specs=[pl.BlockSpec((1, S), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((1, 8), lambda i: (i, 0)),
                    out_shape=x,
                )(x)
        """,
    })
    f = one_finding(run_rules(root, ["PB"]), "PB002")
    assert f.path == "src/repro/kernels/bad_shape.py" and f.line == 7
    assert "dimension `S` is not statically bounded" in f.message


FAMILY_BASE = """
FAMILY_NAMES = ("icws", "toy")


class ICWSFamily:
    name = "icws"
    components = ()

    def storage_doubles_per_row(self):
        return 1.0

    def sketch_rows(self, vecs):
        return ()

    def estimate_fields(self, q, c):
        return None

    def merge_rows(self, a, b):
        return a

    def host_oracle(self):
        return None


class ToyFamily(ICWSFamily):
    name = "toy"
{toy_body}

def make_family(name, *, storage, seed=0):
    if name == "icws":
        return ICWSFamily()
{make_toy}    raise ValueError(name)
"""


def family_fixture(toy_body="", make_toy='    if name == "toy":\n'
                                         '        return ToyFamily()\n',
                   sweeps=True):
    files = {
        "src/repro/data/families.py":
            FAMILY_BASE.format(toy_body=toy_body, make_toy=make_toy),
    }
    if sweeps:
        for rel in ("tests/test_families.py", "tests/test_sharded_query.py",
                    "benchmarks/perf_sketch.py"):
            files[rel] = "from repro.data.families import FAMILY_NAMES\n"
    return files


def test_fc001_family_missing_merge_rows(tmp_path):
    # ToyFamily overrides the contract away: merge_rows deleted by
    # shadowing the base with a non-contract class.
    bad = FAMILY_BASE.format(toy_body="", make_toy='    if name == "toy":\n'
                                                   '        return ToyFamily()\n')
    bad = bad.replace("class ToyFamily(ICWSFamily):\n    name = \"toy\"\n",
                      "class ToyFamily:\n    name = \"toy\"\n"
                      "    components = ()\n"
                      "    def storage_doubles_per_row(self):\n"
                      "        return 1.0\n"
                      "    def sketch_rows(self, vecs):\n"
                      "        return ()\n"
                      "    def estimate_fields(self, q, c):\n"
                      "        return None\n"
                      "    def host_oracle(self):\n"
                      "        return None\n")
    files = family_fixture()
    files["src/repro/data/families.py"] = bad
    root = build_repo(tmp_path, files)
    f = one_finding(run_rules(root, ["FC"]), "FC001")
    assert f.path == "src/repro/data/families.py"
    assert "'toy'" in f.message and "merge_rows" in f.message


def test_fc001_family_with_no_class_at_all(tmp_path):
    files = family_fixture()
    files["src/repro/data/families.py"] = files[
        "src/repro/data/families.py"].replace('name = "toy"', 'label = "toy"')
    root = build_repo(tmp_path, files)
    f = one_finding(run_rules(root, ["FC"]), "FC001")
    assert "no class declaring name='toy'" in f.message


def test_fc002_family_not_constructible(tmp_path):
    files = family_fixture(make_toy="")
    root = build_repo(tmp_path, files)
    f = one_finding(run_rules(root, ["FC"]), "FC002")
    assert "'toy'" in f.message and "make_family" in f.message


def test_fc003_family_missing_from_sweep(tmp_path):
    files = family_fixture()
    files["tests/test_families.py"] = 'for fam in ("icws",):\n    pass\n'
    root = build_repo(tmp_path, files)
    f = one_finding(run_rules(root, ["FC"]), "FC003")
    assert f.path == "tests/test_families.py"
    assert "'toy'" in f.message


def test_fc_contract_dataclass_field_and_bases_resolve(tmp_path):
    # the real-repo idiom: dataclasses.field(default=...) names + same-module
    # base classes supplying contract members
    files = family_fixture()
    files["src/repro/data/families.py"] = """
import dataclasses

FAMILY_NAMES = ("toy",)


class _Base:
    components = ()

    def storage_doubles_per_row(self):
        return 1.0

    def sketch_rows(self, vecs):
        return ()

    def estimate_fields(self, q, c):
        return None

    def merge_rows(self, a, b):
        return a


@dataclasses.dataclass(frozen=True)
class ToyFamily(_Base):
    name: str = dataclasses.field(default="toy", init=False)

    def host_oracle(self):
        return None


def make_family(name, *, storage, seed=0):
    if name == "toy":
        return ToyFamily()
    raise ValueError(name)
"""
    root = build_repo(tmp_path, files)
    assert run_rules(root, ["FC"]).ok


def test_ob001_unwrapped_and_mislabeled_launches(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/kernels/ops.py": """
            from repro import obs as _obs


            @_obs.instrumented("icws_sketch")
            def icws_sketch(x):
                return x


            def icws_estimate(x):
                return x


            @_obs.instrumented("jl_sketch")
            def cs_sketch(x):
                return x


            def _interpret():                  # private helpers: out of scope
                return True
        """,
    })
    result = run_rules(root, ["OB001"])
    assert [f.rule for f in result.findings] == ["OB001", "OB001"]
    by_msg = sorted(f.message for f in result.findings)
    assert "'icws_estimate'" in by_msg[1] and "not wrapped" in by_msg[1]
    assert "'jl_sketch'" in by_msg[0] and "'cs_sketch'" in by_msg[0]
    # alternate decorator spellings all count as coverage
    root2 = build_repo(tmp_path / "ok", {
        "src/repro/kernels/ops.py": """
            from repro.obs import instrumented
            from repro import obs


            @instrumented("icws_sketch")
            def icws_sketch(x):
                return x


            @obs.instrumented("jl_sketch")
            def jl_sketch(x):
                return x
        """,
    })
    assert run_rules(root2, ["OB001"]).ok


def test_ob_rules_noop_on_fixture_trees(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/kernels/common.py": DEVICE_COMMON,
        "src/repro/core/u32.py": HOST_U32,
    })
    assert run_rules(root, ["OB"]).ok


def test_ob002_metrics_md_missing_stale_and_regenerated(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/obs/registry.py": """
            SPECS = (
                {"name": "ops.launches_total", "type": "counter",
                 "labels": ("op", "family"), "unit": "calls",
                 "help": "kernel launches"},
                {"name": "store.rows", "type": "gauge", "labels": ("family",),
                 "unit": "rows", "help": "resident rows"},
            )
        """,
    })
    f = one_finding(run_rules(root, ["OB002"]), "OB002")
    assert f.path == METRICS_MD and "missing" in f.message

    result = run_rules(root, ["OB"])
    assert "`ops.launches_total`" in result.metrics_md
    assert "op, family" in result.metrics_md
    (root / METRICS_MD).write_text(result.metrics_md)
    assert run_rules(root, ["OB"]).ok          # regenerated => clean sweep
    (root / METRICS_MD).write_text("# stale\n")
    f = one_finding(run_rules(root, ["OB002"]), "OB002")
    assert "stale" in f.message


def test_ob002_rejects_non_literal_specs(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/obs/registry.py": """
            def _spec(n):
                return {"name": n}


            SPECS = tuple(_spec(n) for n in ("a.b",))
        """,
    })
    f = one_finding(run_rules(root, ["OB002"]), "OB002")
    assert f.path == "src/repro/obs/registry.py"
    assert "pure-literal" in f.message


def test_baseline_covers_and_bl001_stale(tmp_path):
    root = build_repo(tmp_path, {
        "src/repro/kernels/common.py": DEVICE_COMMON + "LONELY_STREAM = 8\n",
        "src/repro/core/u32.py": HOST_U32,
    })
    baseline = tmp_path / "bl.toml"
    baseline.write_text(textwrap.dedent("""
        [[exempt]]
        rule = "SR003"
        path = "src/repro/kernels/common.py"
        match = "LONELY_STREAM"
        reason = "fixture exception"

        [[exempt]]
        rule = "SR003"
        path = "src/repro/core/nowhere.py"
        reason = "stale on purpose"
    """))
    cfg = Config(root=root, rules=("SR003",), baseline_path=baseline)
    result = run(cfg)
    # rules filter active: the live entry absorbs its finding, the stale
    # entry stays quiet (its rule may simply not have run)
    assert result.ok
    assert [e.rule for _, e in result.baselined] == ["SR003"]

    cfg_all = Config(root=root, baseline_path=baseline)
    rules_fired = {f.rule for f in run(cfg_all).findings}
    assert "BL001" in rules_fired and "SR003" not in rules_fired


def test_baseline_parser_rejects_malformed():
    with pytest.raises(BaselineError):
        parse_baseline('[[exempt]]\nrule = "SR001"\npath = "x.py"\n')  # no reason
    with pytest.raises(BaselineError):
        parse_baseline('[exempt]\nrule = "SR001"\n')
    with pytest.raises(BaselineError):
        parse_baseline('rule = "SR001"\n')
    with pytest.raises(BaselineError):
        parse_baseline('[[exempt]]\nrule = SR001\n')
    assert parse_baseline("# only comments\n") == []


def test_analysis_imports_no_jax():
    """The whole point: the pass must run where jax cannot."""
    banned = [m for m in sys.modules
              if m == "jax" or m.startswith("jax.")]
    import repro.analysis  # noqa: F401
    import repro.analysis.engine  # noqa: F401
    newly = [m for m in sys.modules
             if (m == "jax" or m.startswith("jax.")) and m not in banned]
    assert not newly, f"repro.analysis pulled in jax modules: {newly}"


def test_repo_self_check_is_clean_and_fast():
    """This repo passes its own invariants -- the CI lint gate, in-process.

    Every violation is either fixed or pinned in baseline.toml with a
    written reason; STREAMS.md is current; every pallas_call fits the
    VMEM block budget.
    """
    t0 = time.monotonic()
    result = run(Config(root=REPO_ROOT))
    dt = time.monotonic() - t0
    assert result.ok, "\n".join(f.format() for f in result.findings)
    assert dt < 2.0, f"analysis took {dt:.2f}s, budget is 2s"
    # the baseline is live (flash-attention PB002s) and fully consumed
    assert result.baselined, "expected pinned PB002 exceptions"
    for f, e in result.baselined:
        assert e.reason.strip(), f"baseline entry without reason: {e}"
    # the stream registry proved non-trivial: all five families present
    assert "ICWS_R1_STREAM" in result.streams_md
    assert "SAMPLE_HASH_STREAM" in result.streams_md
    # the metric registry renders and covers the core namespaces
    for needle in ("ops.launches_total", "serve.request_seconds",
                   "quality.ppm_error"):
        assert needle in result.metrics_md, needle
    # budget report covers every kernel family's pallas_call sites
    kernels = {e["kernel"] for e in result.budget_report}
    assert {"icws_sketch_pallas", "estimate_fields_pallas",
            "countsketch_pallas", "jl_sketch_pallas",
            "sample_estimate_fields_pallas"} <= kernels
    assert all(e["within_budget"] for e in result.budget_report)


def test_repo_baseline_loads():
    entries = load_baseline(
        REPO_ROOT / "src" / "repro" / "analysis" / "baseline.toml")
    assert entries and all(e.reason for e in entries)
