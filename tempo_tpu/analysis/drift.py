"""Drift engine: code-vs-docs catalogs as declarations.

PRs 1-9 accumulated three hand-rolled drift tests (config knobs vs
docs/configuration.md, metric names vs docs/observability.md,
faultpoints vs docs/robustness.md), each with its own regex walk over
the source tree. This module re-bases them on the shared parse: a
catalog is ONE :class:`Catalog` declaration — an extractor over the
parsed package, the doc file(s) every extracted name must appear in,
and a sanity floor that catches a broken extractor before it silently
passes an empty set. The legacy tests are thin wrappers now
(tests/test_config_docs.py, test_observability.py, test_faults.py
assert the corresponding catalogs are clean), and a NEW catalog —
knobs, debug routes, faultpoints, metrics — is one entry in
:data:`CATALOGS`.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass

from .core import Checker, Finding, Package

# metric-name prefixes the observability catalog covers (matches the
# legacy grep in tests/test_observability.py)
_METRIC_PREFIXES = ("tempo", "tempodb", "traces", "process")


@dataclass(frozen=True)
class Catalog:
    """One code-vs-docs invariant. ``extract(pkg) -> dict[name, (rel,
    line)]`` walks the shared parse; every extracted name must appear in
    every file of ``docs`` (``backtick=True`` requires `name` form, the
    metric-catalog convention); fewer than ``min_names`` extracted names
    fails the catalog itself — a broken extractor must not pass
    vacuously."""

    name: str
    docs: tuple
    extract: object
    min_names: int = 1
    backtick: bool = False
    hint: str = ""


# ---- extractors (each returns {name: (rel_path, line)}) ----

def _dataclass_fields(pkg: Package, dotted: str, cls: str,
                      prefix_filter: tuple | None = None) -> dict:
    mod = pkg.by_dotted.get(dotted)
    out: dict = {}
    if mod is None:
        return out
    for node in mod.tree.body:
        if not (isinstance(node, ast.ClassDef) and node.name == cls):
            continue
        for stmt in node.body:
            if isinstance(stmt, ast.AnnAssign) \
                    and isinstance(stmt.target, ast.Name):
                name = stmt.target.id
                if prefix_filter is None or name.startswith(prefix_filter):
                    out[name] = (mod.rel, stmt.lineno)
    return out


def tempodb_config_fields(pkg: Package) -> dict:
    return _dataclass_fields(pkg, "tempo_tpu.db.tempodb", "TempoDBConfig")


def robustness_knob_fields(pkg: Package) -> dict:
    """The robustness TempoDBConfig knobs (search_breaker_*,
    robustness_*, the three timeout knobs) — documented in BOTH
    docs/robustness.md and docs/configuration.md."""
    fields = _dataclass_fields(pkg, "tempo_tpu.db.tempodb",
                               "TempoDBConfig")
    keep = {
        n: loc for n, loc in fields.items()
        if n.startswith(("search_breaker_", "robustness_"))
        or n in ("search_device_dispatch_timeout_s",
                 "search_dispatch_lock_timeout_s",
                 "search_request_timeout_s")
    }
    return keep


def yaml_knobs(pkg: Package) -> dict:
    """Every YAML key the config loader reads: ``*.get("<key>")`` in
    cli/config.py (the AST form of the legacy regex)."""
    mod = pkg.by_dotted.get("tempo_tpu.cli.config")
    out: dict = {}
    if mod is None:
        return out
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "get" and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            key = node.args[0].value
            if key and all(c.islower() or c.isdigit() or c == "_"
                           for c in key):
                out.setdefault(key, (mod.rel, node.lineno))
    return out


def metric_names(pkg: Package) -> dict:
    """Every Counter/Gauge/Histogram registered anywhere in the
    package (first-arg string literal with a tempo/tempodb/traces
    prefix)."""
    out: dict = {}
    for mod in pkg.modules:
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) \
                    and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                fn = node.func
                ctor = fn.attr if isinstance(fn, ast.Attribute) else (
                    fn.id if isinstance(fn, ast.Name) else "")
                if ctor in ("Counter", "Gauge", "Histogram") \
                        and node.args[0].value.startswith(
                            _METRIC_PREFIXES):
                    out.setdefault(node.args[0].value,
                                   (mod.rel, node.lineno))
    return out


def debug_routes(pkg: Package) -> dict:
    """Keys of the DEBUG_ROUTES dict in api/http.py — every registered
    /debug route must be documented in the observability doc's route
    index."""
    mod = pkg.by_dotted.get("tempo_tpu.api.http")
    out: dict = {}
    if mod is None:
        return out
    for node in mod.tree.body:
        targets = []
        value = None
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        for tgt in targets:
            if isinstance(tgt, ast.Name) and tgt.id == "DEBUG_ROUTES" \
                    and isinstance(value, ast.Dict):
                for k in value.keys:
                    if isinstance(k, ast.Constant) \
                            and isinstance(k.value, str):
                        out[k.value] = (mod.rel, k.lineno)
    return out


def faultpoints(pkg: Package) -> dict:
    """Keys of the CATALOG dict in robustness/faults.py."""
    mod = pkg.by_dotted.get("tempo_tpu.robustness.faults")
    out: dict = {}
    if mod is None:
        return out
    for node in mod.tree.body:
        targets = []
        value = None
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        for tgt in targets:
            if isinstance(tgt, ast.Name) and tgt.id == "CATALOG" \
                    and isinstance(value, ast.Dict):
                for k in value.keys:
                    if isinstance(k, ast.Constant) \
                            and isinstance(k.value, str):
                        out[k.value] = (mod.rel, k.lineno)
    return out


CATALOGS = (
    Catalog(
        name="config-fields",
        docs=("docs/configuration.md",),
        extract=tempodb_config_fields,
        min_names=30,
        hint="document the knob in docs/configuration.md, or list it "
             "under the constructor-only / renamed-knob sections",
    ),
    Catalog(
        name="yaml-knobs",
        docs=("docs/configuration.md",),
        extract=yaml_knobs,
        min_names=30,
        hint="document the YAML key in docs/configuration.md",
    ),
    Catalog(
        name="metric-names",
        docs=("docs/observability.md",),
        extract=metric_names,
        min_names=30,
        backtick=True,
        hint="add the metric to the docs/observability.md catalog table",
    ),
    Catalog(
        name="faultpoints",
        docs=("docs/robustness.md",),
        extract=faultpoints,
        min_names=8,
        backtick=True,
        hint="add the faultpoint to the docs/robustness.md catalog",
    ),
    Catalog(
        name="debug-routes",
        docs=("docs/observability.md",),
        extract=debug_routes,
        min_names=8,
        backtick=True,
        hint="document the route in docs/observability.md's /debug "
             "route index",
    ),
    Catalog(
        name="robustness-knobs",
        docs=("docs/robustness.md", "docs/configuration.md"),
        extract=robustness_knob_fields,
        min_names=8,
        hint="robustness knobs are documented in BOTH docs/robustness.md"
             " and docs/configuration.md",
    ),
)


# one parsed package per process: the legacy drift tests each wrap one
# catalog, and re-parsing 115 modules per test would waste tier-1 time
_PKG_CACHE: dict = {}


def catalog_findings(name: str, pkg_dir: str | None = None) -> list:
    """Run ONE catalog over the package — the entry the legacy drift
    tests (test_config_docs, test_observability, test_faults) wrap.
    Returns the findings; empty means the catalog is clean."""
    if pkg_dir is None:
        pkg_dir = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
    pkg_dir = os.path.abspath(pkg_dir)
    pkg = _PKG_CACHE.get(pkg_dir)
    if pkg is None:
        pkg = _PKG_CACHE[pkg_dir] = Package.load(pkg_dir)
    cats = [c for c in CATALOGS if c.name == name]
    if not cats:
        raise KeyError(f"no catalog named {name!r}; have "
                       f"{[c.name for c in CATALOGS]}")
    return DriftChecker(catalogs=cats).check(pkg)


class DriftChecker(Checker):
    id = "drift"

    def __init__(self, catalogs=CATALOGS):
        self.catalogs = tuple(catalogs)

    def check(self, pkg: Package) -> list[Finding]:
        findings: list[Finding] = []
        doc_cache: dict[str, str | None] = {}

        def doc_text(rel: str) -> str | None:
            if rel not in doc_cache:
                path = os.path.join(pkg.root, rel)
                if os.path.exists(path):
                    with open(path, encoding="utf-8") as f:
                        doc_cache[rel] = f.read()
                else:
                    doc_cache[rel] = None
            return doc_cache[rel]

        for cat in self.catalogs:
            names = cat.extract(pkg)
            if len(names) < cat.min_names:
                findings.append(Finding(
                    checker=self.id, path="tempo_tpu/analysis/drift.py",
                    line=1,
                    message=(f"catalog {cat.name!r} extracted only "
                             f"{len(names)} name(s) (floor "
                             f"{cat.min_names}) — the extractor looks "
                             "broken"),
                    hint="fix the extractor (or the floor) in "
                         "tempo_tpu/analysis/drift.py",
                    key=f"floor:{cat.name}"))
                continue
            for doc_rel in cat.docs:
                doc = doc_text(doc_rel)
                if doc is None:
                    findings.append(Finding(
                        checker=self.id, path=doc_rel, line=1,
                        message=f"catalog {cat.name!r}: doc file "
                                f"{doc_rel} is missing",
                        hint=cat.hint, key=f"missing-doc:{cat.name}:"
                                           f"{doc_rel}"))
                    continue
                for name in sorted(names):
                    needle = f"`{name}`" if cat.backtick else name
                    if needle not in doc:
                        rel, line = names[name]
                        findings.append(Finding(
                            checker=self.id, path=rel, line=line,
                            message=(f"{cat.name}: {name!r} is in the "
                                     f"code but not in {doc_rel}"),
                            hint=cat.hint,
                            key=f"{cat.name}:{name}:{doc_rel}"))
        return findings
