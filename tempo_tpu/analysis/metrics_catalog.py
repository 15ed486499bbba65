"""Metric-catalog checker: every metric the package registers has a
docs/observability.md catalog row, and every label a write site uses is
one that row declares.

The drift engine already pins metric NAMES into the docs (the
``metric-names`` catalog); what it cannot see is the label schema — a
call site adding an undocumented label mints a new series per value and
the catalog table silently lies about the metric's cardinality. This
checker closes that gap with two passes over the shared parse:

``uncatalogued-metric``
    a ``Counter``/``Gauge``/``Histogram`` constructed with a
    ``tempo*``-prefixed name that has no row in the observability
    catalog tables;

``unknown-label``
    a write/read call on a registered metric (``inc``, ``observe``,
    ``observe_bulk``, ``set``, ``add``, ``remove``, ``value``,
    ``labels``, ``time``) passing a literal keyword label the metric's
    catalog row does not declare. Dynamic ``**labels`` expansions are
    skipped — only literal keywords are checkable statically.

The docs side is the existing catalog-table convention — rows of
``| `name` | type | labels | meaning |`` where the labels cell holds
backticked label names (``—`` for none). The checker parses those rows
straight out of the markdown; the fixture self-tests inject a catalog
dict instead so they need no doc file.
"""

from __future__ import annotations

import ast
import os
import re

from .core import Checker, Finding, Package

# prefixes the observability catalog covers (mirrors drift.metric_names)
_METRIC_PREFIXES = ("tempo", "tempodb", "traces", "process")
_CTORS = ("Counter", "Gauge", "Histogram")

# every metric method whose **kwargs are label names
_LABELED_METHODS = ("inc", "observe", "observe_bulk", "set", "add",
                    "remove", "value", "labels", "time")

# receivers metric vars are reached through at call sites: the
# package-wide idiom is `obs.<metric>.<method>` (metrics module imported
# as obs/metrics), plus bare names inside the defining module
_RECEIVER_BASES = ("obs", "metrics")

# one catalog row: | `tempo_x_total` | counter | `a`, `b` | meaning |
_ROW_RE = re.compile(
    r"^\|\s*`(?P<name>[A-Za-z_][A-Za-z0-9_:]*)`\s*"
    r"\|\s*(?P<type>counter|gauge|histogram)\s*"
    r"\|(?P<labels>[^|]*)\|")
_LABEL_RE = re.compile(r"`([A-Za-z_][A-Za-z0-9_]*)`")


def parse_doc_catalog(text: str) -> dict:
    """``{metric_name: frozenset(label_names)}`` from every catalog
    table row in the doc. Rows outside the name/type/labels shape
    (e.g. the per-stage meaning tables) simply don't match."""
    out: dict = {}
    for line in text.splitlines():
        m = _ROW_RE.match(line.strip())
        if m is None:
            continue
        labels = frozenset(_LABEL_RE.findall(m.group("labels")))
        out.setdefault(m.group("name"), labels)
    return out


def _ctor_name(fn: ast.AST) -> str:
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return ""


class MetricsCatalogChecker(Checker):
    id = "metrics-catalog"

    def __init__(self, catalog: dict | None = None,
                 doc_rel: str = "docs/observability.md"):
        self._catalog = catalog
        self.doc_rel = doc_rel

    def check(self, pkg: Package) -> list[Finding]:
        catalog = self._catalog
        if catalog is None:
            path = os.path.join(pkg.root, self.doc_rel)
            if not os.path.exists(path):
                return [Finding(
                    checker=self.id, path=self.doc_rel, line=1,
                    message=f"metric catalog doc {self.doc_rel} is "
                            "missing — every registered metric needs a "
                            "catalog row",
                    hint="restore the doc (or construct the checker "
                         "with an explicit catalog)",
                    key=f"missing-doc:{self.doc_rel}")]
            with open(path, encoding="utf-8") as f:
                catalog = parse_doc_catalog(f.read())
        findings: list[Finding] = []

        # pass 1: constructors — var name -> metric name(s), and every
        # registered metric must have a catalog row
        var_to_metrics: dict = {}
        defined_in: dict = {}
        for mod in pkg.modules:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Assign):
                    continue
                call = node.value
                if not (isinstance(call, ast.Call)
                        and _ctor_name(call.func) in _CTORS
                        and call.args
                        and isinstance(call.args[0], ast.Constant)
                        and isinstance(call.args[0].value, str)):
                    continue
                mname = call.args[0].value
                if not mname.startswith(_METRIC_PREFIXES):
                    continue
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        var_to_metrics.setdefault(
                            tgt.id, set()).add(mname)
                        defined_in.setdefault(tgt.id, set()).add(
                            mod.dotted)
                if mname not in catalog:
                    findings.append(Finding(
                        checker=self.id, path=mod.rel, line=node.lineno,
                        message=(f"metric {mname!r} is registered but "
                                 f"has no catalog row in "
                                 f"{self.doc_rel}"),
                        hint="add a `| `name` | type | labels | "
                             "meaning |` row to the catalog table",
                        key=f"uncatalogued:{mname}"))

        # pass 2: write/read sites — literal keyword labels must be
        # catalogued for the metric behind the receiver
        for mod in pkg.modules:
            for node in ast.walk(mod.tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in _LABELED_METHODS):
                    continue
                recv = node.func.value
                if isinstance(recv, ast.Attribute) \
                        and isinstance(recv.value, ast.Name) \
                        and recv.value.id in _RECEIVER_BASES:
                    var = recv.attr
                elif isinstance(recv, ast.Name) \
                        and mod.dotted in defined_in.get(recv.id, ()):
                    var = recv.id
                else:
                    continue
                metrics = var_to_metrics.get(var)
                if not metrics:
                    continue
                # a var bound to several metric names (none today)
                # accepts the union — ambiguity must not manufacture
                # false positives
                allowed: set = set()
                catalogued = [m for m in metrics if m in catalog]
                if not catalogued:
                    continue        # already flagged as uncatalogued
                for m in catalogued:
                    allowed |= catalog[m]
                for kw in node.keywords:
                    if kw.arg is None or kw.arg in allowed:
                        continue
                    mname = sorted(catalogued)[0]
                    findings.append(Finding(
                        checker=self.id, path=mod.rel, line=node.lineno,
                        message=(f"label {kw.arg!r} passed to "
                                 f"{var}.{node.func.attr}() is not in "
                                 f"{mname!r}'s catalog row "
                                 f"(catalogued: "
                                 f"{sorted(allowed) or '—'})"),
                        hint=f"add `{kw.arg}` to the metric's labels "
                             f"cell in {self.doc_rel}, or drop the "
                             "label",
                        key=f"unknown-label:{mname}:{kw.arg}"))
        return findings
