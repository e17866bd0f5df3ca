"""CB* compat-boundary checker.

``repro/compat.py`` is the single module allowed to spell the jax mesh
APIs that have moved between jax releases: ``shard_map`` (once
``jax.experimental.shard_map`` with ``check_rep``, now ``jax.shard_map``
with ``check_vma``), ``jax.sharding.AxisType`` and ``jax.make_mesh(
axis_types=...)``.  Keeping every spelling there means the next move is a
one-module change; these rules catch a direct use at lint time.  CB004 additionally pins the ``interpret=True`` dispatch
convention: kernels decide interpret-vs-TPU at runtime through
``repro.kernels.ops._interpret()``, so a hardcoded ``interpret=True`` call
site under ``src/`` would pin a production path to the emulator.
"""
from __future__ import annotations

import ast
from typing import List

from . import config as cfg_mod
from .astutil import Repo, dotted_name
from .findings import Finding

# Dotted attribute chains that must not appear outside compat.py.  Matched
# against full attribute chains and against `from X import Y` forms.
_GATED_ATTRS = {
    "jax.shard_map": "CB001",
    "jax.experimental.shard_map": "CB001",
    "jax.experimental.shard_map.shard_map": "CB001",
    "jax.sharding.AxisType": "CB002",
    "jax.make_mesh": "CB003",
}
# (module, name) pairs for ImportFrom.
_GATED_IMPORTS = {
    ("jax", "shard_map"): "CB001",
    ("jax.experimental", "shard_map"): "CB001",
    ("jax.experimental.shard_map", "shard_map"): "CB001",
    ("jax.sharding", "AxisType"): "CB002",
    ("jax", "make_mesh"): "CB003",
}


def check(repo: Repo) -> List[Finding]:
    findings: List[Finding] = []
    for pf in repo.files:
        if not pf.rel.startswith("src/") or pf.rel == cfg_mod.COMPAT_MODULE:
            continue
        for node in ast.walk(pf.tree):
            if isinstance(node, ast.Attribute):
                chain = dotted_name(node)
                rule = _GATED_ATTRS.get(chain or "")
                if rule:
                    findings.append(Finding(
                        rule, pf.rel, node.lineno,
                        f"direct {chain} use; route through "
                        f"repro.compat"))
            elif isinstance(node, ast.ImportFrom) and node.module:
                for alias in node.names:
                    rule = _GATED_IMPORTS.get((node.module, alias.name))
                    if rule:
                        findings.append(Finding(
                            rule, pf.rel, node.lineno,
                            f"direct `from {node.module} import "
                            f"{alias.name}`; route through repro.compat"))
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.startswith("jax.experimental.shard_map"):
                        findings.append(Finding(
                            "CB001", pf.rel, node.lineno,
                            f"direct `import {alias.name}`; route through "
                            f"repro.compat"))
            elif isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg == "interpret" \
                            and isinstance(kw.value, ast.Constant) \
                            and kw.value.value is True:
                        findings.append(Finding(
                            "CB004", pf.rel, kw.value.lineno,
                            "hardcoded interpret=True call site; dispatch "
                            "via repro.kernels.ops._interpret()"))
    return findings
