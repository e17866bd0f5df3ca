"""FC* family-contract checker.

``FAMILY_NAMES`` in ``repro/data/families.py`` is the single source of
truth for which serving families exist; everything downstream -- corpus
stores, the sharded query engine, the storage-matched benchmarks, the
parameterized test sweeps -- iterates it.  A sixth family added to the
tuple without a complete ``SketchFamily`` implementation (or vice versa) is
exactly the half-registered state that passes whatever tests exist and
fails in serving.  This checker proves, per name in ``FAMILY_NAMES``:

* FC001 -- a class in the module declares ``name = "<family>"`` and
  (transitively through same-module bases) implements the full contract:
  ``components``, ``storage_doubles_per_row``, ``sketch_rows``,
  ``estimate_fields``, ``merge_rows``, ``host_oracle``.
* FC002 -- ``make_family`` can construct it (the name appears as a string
  constant in its body).
* FC003 -- every parameterized sweep covers it (the sweep file iterates
  ``FAMILY_NAMES`` or quotes the name, including inside embedded
  subprocess scripts).
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from . import config as cfg_mod
from .astutil import Repo, dotted_name
from .findings import Finding

CONTRACT_MEMBERS = (
    "components",
    "storage_doubles_per_row",
    "sketch_rows",
    "estimate_fields",
    "merge_rows",
    "host_oracle",
)


def _family_names(tree: ast.AST) -> Optional[Tuple[List[str], int]]:
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "FAMILY_NAMES" \
                and isinstance(node.value, (ast.Tuple, ast.List)):
            names = []
            for elt in node.value.elts:
                if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                    names.append(elt.value)
            return names, node.lineno
    return None


def _declared_family(cls: ast.ClassDef) -> Optional[str]:
    """The family name a class declares: ``name = "cs"`` or the dataclass
    idiom ``name: str = dataclasses.field(default="cs", init=False)``."""
    for stmt in cls.body:
        target = None
        value = None
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name):
            target, value = stmt.targets[0].id, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and isinstance(stmt.target, ast.Name):
            target, value = stmt.target.id, stmt.value
        if target != "name" or value is None:
            continue
        if isinstance(value, ast.Constant) and isinstance(value.value, str):
            return value.value
        if isinstance(value, ast.Call):
            callee = dotted_name(value.func) or ""
            if callee.split(".")[-1] == "field":
                for kw in value.keywords:
                    if kw.arg == "default" \
                            and isinstance(kw.value, ast.Constant) \
                            and isinstance(kw.value.value, str):
                        return kw.value.value
    return None


def _own_members(cls: ast.ClassDef) -> Set[str]:
    members: Set[str] = set()
    for stmt in cls.body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            members.add(stmt.name)
        elif isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    members.add(t.id)
        elif isinstance(stmt, ast.AnnAssign) and isinstance(stmt.target, ast.Name):
            members.add(stmt.target.id)
    return members


def _all_members(cls: ast.ClassDef, by_name: Dict[str, ast.ClassDef],
                 seen: Optional[Set[str]] = None) -> Set[str]:
    seen = seen or set()
    if cls.name in seen:
        return set()
    seen.add(cls.name)
    members = _own_members(cls)
    for base in cls.bases:
        base_name = dotted_name(base)
        if base_name and base_name in by_name:
            members |= _all_members(by_name[base_name], by_name, seen)
    return members


def check(repo: Repo) -> List[Finding]:
    findings: List[Finding] = []
    pf = repo.get(cfg_mod.FAMILIES_MODULE)
    if pf is None:
        findings.append(Finding(
            "FC001", cfg_mod.FAMILIES_MODULE, 1,
            "families module not found; FAMILY_NAMES contract unverifiable"))
        return findings
    got = _family_names(pf.tree)
    if got is None:
        findings.append(Finding(
            "FC001", pf.rel, 1,
            "FAMILY_NAMES tuple of string literals not found"))
        return findings
    names, names_line = got

    classes = {node.name: node for node in ast.walk(pf.tree)
               if isinstance(node, ast.ClassDef)}
    by_family: Dict[str, ast.ClassDef] = {}
    for cls in classes.values():
        fam = _declared_family(cls)
        if fam is not None:
            by_family[fam] = cls

    # FC001: complete SketchFamily implementation per name.
    for fam in names:
        cls = by_family.get(fam)
        if cls is None:
            findings.append(Finding(
                "FC001", pf.rel, names_line,
                f"family {fam!r} has no class declaring name={fam!r}"))
            continue
        missing = sorted(set(CONTRACT_MEMBERS)
                         - _all_members(cls, classes))
        if missing:
            findings.append(Finding(
                "FC001", pf.rel, cls.lineno,
                f"family {fam!r} ({cls.name}) is missing contract "
                f"member(s): {', '.join(missing)}"))

    # FC002: constructible via make_family.
    make = None
    for node in ast.walk(pf.tree):
        if isinstance(node, ast.FunctionDef) and node.name == "make_family":
            make = node
            break
    if make is None:
        findings.append(Finding(
            "FC002", pf.rel, names_line, "make_family() not found"))
    else:
        literals = {n.value for n in ast.walk(make)
                    if isinstance(n, ast.Constant) and isinstance(n.value, str)}
        for fam in names:
            if fam not in literals:
                findings.append(Finding(
                    "FC002", pf.rel, make.lineno,
                    f"family {fam!r} is not constructible via "
                    f"make_family()"))

    # FC003: parameterized sweep coverage.
    for rel in cfg_mod.SWEEP_FILES:
        sweep = repo.get(rel)
        if sweep is None:
            findings.append(Finding(
                "FC003", rel, 1,
                f"sweep file missing; cannot verify coverage of "
                f"{', '.join(names)}"))
            continue
        if "FAMILY_NAMES" in sweep.source:
            continue    # iterates the registry itself: future-proof
        for fam in names:
            if f'"{fam}"' in sweep.source or f"'{fam}'" in sweep.source:
                continue
            findings.append(Finding(
                "FC003", rel, 1,
                f"family {fam!r} missing from this parameterized sweep "
                f"(iterate FAMILY_NAMES to stay future-proof)"))
    return findings
