"""Tier-1 static-analysis suite (tempo_tpu/analysis/ + scripts/check.py).

Two directions per checker:
  - the REAL package is clean: zero un-allowlisted findings, zero stale
    allowlist entries (the suite-at-zero-by-construction contract);
  - the known-bad fixture package (tests/fixtures/analysis_bad/) is
    flagged: the PR 1 rendezvous-deadlock lock cycle by the lock-order
    analyzer, the gate-violating noop path by the contract checker, the
    tracer .item() in a jit body by the purity lint — and the clean
    twins in the same files stay unflagged (precision, not just recall).

Plus the CLI/CI surface (exit codes, --json), allowlist semantics
(stale entries fail, justifications are mandatory, fingerprints survive
line drift), the <10s single-parse-pass runtime contract, and
mypy --strict over the annotated core subset (skipped where mypy is not
installed — the container bakes no new deps).
"""

import json
import os
import subprocess
import sys
import time

import pytest

from tempo_tpu.analysis import (
    default_checkers,
    load_allowlist,
    run_suite,
)
from tempo_tpu.analysis.allowlist import (
    AllowlistError,
    _parse_subset,
    default_path,
)
from tempo_tpu.analysis.core import Finding, Package
from tempo_tpu.analysis.contracts import GatedFunction, NoopContractChecker
from tempo_tpu.analysis.jit_purity import JitPurityChecker
from tempo_tpu.analysis.locks import LockOrderChecker

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_PKG = os.path.join(_ROOT, "tempo_tpu")
_FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.fixture(scope="module")
def real_pkg():
    return Package.load(_PKG)


@pytest.fixture(scope="module")
def bad_pkg():
    return Package.load(os.path.join(_FIXTURES, "analysis_bad"),
                        rel_base=_FIXTURES)


# ------------------------------------------------------------ the suite


def test_suite_clean_over_package(real_pkg):
    """THE tier-1 gate: all four checkers over tempo_tpu/, zero
    un-allowlisted findings, zero stale allowlist entries, single parse
    pass, under 10 seconds."""
    t0 = time.perf_counter()
    report = run_suite(real_pkg, default_checkers(),
                       load_allowlist(default_path()))
    elapsed = time.perf_counter() - t0
    assert not report.findings, (
        "static-analysis findings (fix them, or add a justified "
        "allowlist entry):\n" + report.render())
    assert not report.stale, (
        "stale allowlist entries (the defect they justified is gone — "
        "delete them):\n" + report.render())
    assert report.exit_code == 0
    assert elapsed < 10.0, f"suite took {elapsed:.1f}s (contract: <10s)"


def test_allowlist_entries_all_carry_justifications():
    allowlist = load_allowlist(default_path())
    for e in allowlist.entries:
        assert e.justification.strip(), e.fingerprint
        assert len(e.justification) > 20, (
            f"{e.fingerprint}: a justification must say WHY, not just "
            "wave")


# ------------------------------------------- lock-order (PR 1 fixture)


def test_lock_order_flags_rendezvous_deadlock_cycle(bad_pkg):
    findings = LockOrderChecker().check(bad_pkg)
    cycles = [f for f in findings if f.key.startswith("cycle:")]
    # ONE strongly connected component: the direct A<->B cycle and the
    # B<->enqueue cycle share queue_lock_b, so Tarjan reports them as
    # one deadlock-prone lock cluster
    assert len(cycles) == 1, [f.message for f in findings]
    msg = cycles[0].message
    assert "queue_lock_a" in msg and "queue_lock_b" in msg
    # enqueue_lock is only reachable through the context-manager helper
    # (the locked_collective shape): its presence in the SCC proves
    # with-item helper acquisitions propagate into caller summaries
    assert "enqueue_lock" in msg
    assert "deadlock" in msg


def test_lock_order_flags_blocking_under_lock(bad_pkg):
    findings = LockOrderChecker().check(bad_pkg)
    blocking = sorted((f for f in findings
                       if f.key.startswith("blocking:")),
                      key=lambda f: f.line)
    msgs = [f.message for f in blocking]
    assert len(blocking) == 2, msgs
    assert "wait_under_lock" in msgs[0] and ".result" in msgs[0]
    # result(None) is explicitly unbounded — an argument being present
    # must not pass for a bounding timeout
    assert "wait_none_under_lock" in msgs[1]
    # acquire(blocking=False) returns immediately: the clean twin
    assert not [f for f in findings
                if "clean_try_acquire" in f.message]


def test_lock_order_flags_reacquire_through_call(bad_pkg):
    findings = LockOrderChecker().check(bad_pkg)
    re_acq = [f for f in findings if f.key.startswith("reacquire:")]
    assert len(re_acq) == 1, [f.message for f in findings]
    assert "self-deadlock" in re_acq[0].message


def test_lock_order_clean_twin_not_flagged(bad_pkg):
    """clean_dispatch: consistent order + bounded result() — silent."""
    findings = LockOrderChecker().check(bad_pkg)
    assert not [f for f in findings if "clean_dispatch" in f.message]


def test_lock_order_clean_on_real_package(real_pkg):
    """The PR-level contract: the real lock graph is cycle-free and no
    blocking call survives under a lock (the fence/_FusedOut fixes)."""
    assert LockOrderChecker().check(real_pkg) == []


def test_the_batchers_holds_of_the_caches_lock_are_seen(real_pkg):
    """`BlockBatcher` keeps no lock of its own: its plan cache, prune
    memo and interest counts are guarded by the staged-group cache's
    `group_lock`, named through `self.cache` or a local `cache`. The
    analyzer resolves both to that one lock (by the attribute's unique
    name), so the search loop's holds stay in the graph it checks."""
    import ast

    from tempo_tpu.analysis.locks import _Symbols

    sym = _Symbols(real_pkg)
    mod = next(m for m in real_pkg.modules
               if m.dotted == "tempo_tpu.search.batcher")
    want = "tempo_tpu.search.group_cache:GroupCache.group_lock"
    for expr in ("self.cache.group_lock", "cache.group_lock"):
        node = ast.parse(expr, mode="eval").body
        assert sym.resolve_lock(mod, "BlockBatcher", node, {}) == want
    holds = [n for n in ast.walk(mod.tree) if isinstance(n, ast.With)
             and any(sym.resolve_lock(mod, "BlockBatcher", i.context_expr,
                                      {}) == want for i in n.items)]
    assert len(holds) >= 10


# ------------------------------------------------- noop-contract


_FIXTURE_GATES = (
    GatedFunction("analysis_bad.noop_gate", "Telemetry.record_thing",
                  ("enabled",), "fixture_knob"),
    GatedFunction("analysis_bad.noop_gate", "Telemetry.record_clean",
                  ("enabled",), "fixture_knob"),
)


def test_contract_flags_pre_gate_work_and_unguarded_calls(bad_pkg):
    findings = NoopContractChecker(gated=_FIXTURE_GATES).check(bad_pkg)
    keys = sorted(f.key.split(":")[0] for f in findings)
    assert keys == ["pre-gate", "pre-gate"] + ["unguarded"] * 7, \
        [f.message for f in findings]
    msgs = " | ".join(f.message for f in findings)
    assert "metric write" in msgs and "clock read" in msgs
    assert "FAULTS.hit()" in msgs and "TELEMETRY.record_age()" in msgs
    # the hedge-timer rule: an estimator touch without the armed gate
    # is flagged; the guarded twin stays silent
    assert "hedge_unguarded" in msgs and "HEDGE.observe()" in msgs
    assert "hedge_guarded" not in msgs
    # the analytics rule: staging without the enabled gate is flagged;
    # the guarded twin stays silent
    assert "analytics_unguarded" in msgs
    assert "ANALYTICS.stage_for_batch()" in msgs
    assert "analytics_guarded" not in msgs
    # polarity: `if FAULTS.active: return` exits on the ARMED path —
    # it must NOT count as a guard for what follows; and the else
    # branch of a gate test is the gate-OFF path
    assert "hit_inverted_gate" in msgs and "hit_in_else" in msgs
    # a record call used as a context manager is still a record call
    assert "record_with_item" in msgs and "record_span" in msgs
    # the good twins stay silent
    assert "record_clean" not in msgs and "hit_guarded" not in msgs


def test_contract_registry_drift_is_a_finding(bad_pkg):
    gone = (GatedFunction("analysis_bad.noop_gate", "Telemetry.deleted",
                          ("enabled",), "fixture_knob"),)
    findings = NoopContractChecker(gated=gone, guarded=()).check(bad_pkg)
    assert any(f.key.startswith("gate-missing:") for f in findings)


# ------------------------------------------------- jit-purity


def test_jit_purity_flags_tracer_leaks(bad_pkg):
    findings = JitPurityChecker().check(bad_pkg)
    kinds = sorted(f.key.split(":")[0] for f in findings
                   if "leaky_kernel" in f.key)
    assert kinds == sorted(["clock", "tracer-branch", "item",
                            "np-host", "scalar-sync"]), \
        [f.message for f in findings]


def test_jit_purity_flags_missing_static_decl(bad_pkg):
    findings = JitPurityChecker().check(bad_pkg)
    decl = [f for f in findings if f.key.startswith("static-decl:")]
    assert len(decl) == 1 and "top_k" in decl[0].message


def test_jit_purity_clean_twin_not_flagged(bad_pkg):
    findings = JitPurityChecker().check(bad_pkg)
    assert not [f for f in findings if "clean_kernel" in f.message], \
        [f.message for f in findings]


def test_jit_purity_flags_tainted_width_descriptor(bad_pkg):
    findings = JitPurityChecker().check(bad_pkg)
    taint = [f for f in findings if f.key.startswith("descriptor-taint:")
             and "descriptor_taint_kernel" in f.key]
    assert taint and "'w'" in taint[0].message, \
        [f.message for f in findings]
    assert not [f for f in findings
                if "descriptor_clean_kernel" in f.key], \
        [f.message for f in findings]


def test_jit_purity_flags_tainted_plan_descriptor(bad_pkg):
    """The structural engine's static plan descriptors are covered by
    the same rule as the packed-residency widths: tracer data reaching
    a plan-dispatching helper is flagged; the static twin stays
    silent."""
    findings = JitPurityChecker().check(bad_pkg)
    taint = [f for f in findings if f.key.startswith("descriptor-taint:")
             and "plan_taint_kernel" in f.key]
    assert taint and "'plan'" in taint[0].message, \
        [f.message for f in findings]
    assert not [f for f in findings if "plan_clean_kernel" in f.key], \
        [f.message for f in findings]


def test_jit_purity_flags_tainted_span_layout_descriptor(bad_pkg):
    """The span-sharding layout flag is a descriptor like widths/plan:
    tracer data reaching a layout-dispatching helper is flagged; the
    static twin stays silent."""
    findings = JitPurityChecker().check(bad_pkg)
    taint = [f for f in findings if f.key.startswith("descriptor-taint:")
             and "span_layout_taint_kernel" in f.key]
    assert taint and "'span_sharded'" in taint[0].message, \
        [f.message for f in findings]
    assert not [f for f in findings
                if "span_layout_clean_kernel" in f.key], \
        [f.message for f in findings]


def test_jit_purity_flags_tainted_bucket_descriptor(bad_pkg):
    """The shape-bucket descriptor (bucketed cross-plan stacking) is a
    descriptor like widths/plan/span_sharded: tracer data reaching a
    bucket-dispatching helper is flagged; the static twin stays
    silent."""
    findings = JitPurityChecker().check(bad_pkg)
    taint = [f for f in findings if f.key.startswith("descriptor-taint:")
             and "bucket_taint_kernel" in f.key]
    assert taint and "'bucket'" in taint[0].message, \
        [f.message for f in findings]
    assert not [f for f in findings
                if "bucket_clean_kernel" in f.key], \
        [f.message for f in findings]


def test_jit_purity_flags_tainted_tier_descriptor(bad_pkg):
    """The hot-tier page-capacity descriptor is a descriptor like
    widths/plan/span_sharded: tracer data reaching a tier-dispatching
    helper is flagged; the static twin stays silent."""
    findings = JitPurityChecker().check(bad_pkg)
    taint = [f for f in findings if f.key.startswith("descriptor-taint:")
             and "tier_taint_kernel" in f.key]
    assert taint and "'tier'" in taint[0].message, \
        [f.message for f in findings]
    assert not [f for f in findings
                if "tier_clean_kernel" in f.key], \
        [f.message for f in findings]


def test_contract_live_tier_gates_registered():
    """The hot-tier gate is pinned by BOTH registries: every LiveTier
    hook tests `enabled` first (GatedFunction) and the ingest/search
    call sites are dominated by the gate read (GuardedCall) — the
    checker run over the real package enforces them; this test pins
    that the entries exist so a refactor cannot silently drop the
    noop contract."""
    from tempo_tpu.analysis.contracts import (GATED_FUNCTIONS,
                                              GUARDED_CALLS)

    gated = {(g.qualname, g.knob) for g in GATED_FUNCTIONS}
    for hook in ("absorb", "mark_cut", "mark_poll_visible",
                 "poll_visible", "search", "subscribe", "unsubscribe",
                 "has_subscribers", "notify_push"):
        assert (f"LiveTier.{hook}", "search_live_tier_enabled") in gated
    guarded = {(m, g.knob) for g in GUARDED_CALLS for m in g.methods}
    for m in ("absorb", "mark_cut", "search", "mark_poll_visible",
              "subscribe", "unsubscribe", "notify_push"):
        assert (m, "search_live_tier_enabled") in guarded


def test_contract_new_structural_gates_registered():
    """The stacking and sharding gates are pinned by BOTH registries:
    the gate functions test their attribute first (GatedFunction) and
    every call site is dominated by the gate read (GuardedCall) — the
    checker run over the real package (test_suite_clean_over_package)
    enforces them; this test pins that the entries exist so a refactor
    cannot silently drop the contract."""
    from tempo_tpu.analysis.contracts import (GATED_FUNCTIONS,
                                              GUARDED_CALLS)

    gated = {(g.qualname, g.knob) for g in GATED_FUNCTIONS}
    assert ("StructuralGate.stack_group_key",
            "search_structural_stack_enabled") in gated
    assert ("StructuralGate.shard_span_segment",
            "search_structural_shard_spans") in gated
    assert ("StructuralGate.bucket_group_key",
            "search_structural_bucket_enabled") in gated
    assert ("StructuralGate.remainder_pad",
            "search_structural_remainder_pages") in gated
    guarded = {(m, g.knob) for g in GUARDED_CALLS for m in g.methods}
    assert ("stack_group_key",
            "search_structural_stack_enabled") in guarded
    assert ("shard_span_segment",
            "search_structural_shard_spans") in guarded
    assert ("remainder_pad",
            "search_structural_remainder_pages") in guarded


def test_contract_selftrace_gates_registered():
    """The dogfood gate is pinned by BOTH registries: the annotation /
    recorder entry points test their gate attribute first
    (GatedFunction) and the hot-path call sites are dominated by the
    one-attribute gate read (GuardedCall) — the checker run over the
    real package enforces them; this pins that the entries exist so a
    refactor cannot silently drop the noop contract. So are the spans
    written after the fact and the device timeline's watcher: with no
    tracer installed no call site reaches them."""
    from tempo_tpu.analysis.contracts import (GATED_FUNCTIONS,
                                              GUARDED_CALLS)

    gated = {(g.qualname, g.knob) for g in GATED_FUNCTIONS}
    assert ("SelfTraceGate.annotate_query",
            "selftrace_ingest_enabled") in gated
    assert ("FlightRecorder.record",
            "selftrace_ingest_enabled") in gated
    guarded = {(m, g.knob) for g in GUARDED_CALLS for m in g.methods}
    assert ("annotate_query", "selftrace_ingest_enabled") in guarded
    assert ("record_span", "self_tracing") in guarded
    assert ("watch", "self_tracing") in guarded
    assert ("record", "selftrace_ingest_enabled") in guarded


def test_jit_purity_clean_on_real_kernels(real_pkg):
    assert JitPurityChecker().check(real_pkg) == []


# ------------------------------------------------- metrics-catalog


_FIXTURE_METRIC_CATALOG = {
    "tempo_fixture_good_total": frozenset({"tenant"}),
}


def test_metrics_catalog_flags_uncatalogued_metric(bad_pkg):
    from tempo_tpu.analysis.metrics_catalog import MetricsCatalogChecker

    findings = MetricsCatalogChecker(
        catalog=_FIXTURE_METRIC_CATALOG).check(bad_pkg)
    missing = [f for f in findings if f.key.startswith("uncatalogued:")]
    assert len(missing) == 1, [f.message for f in findings]
    assert "tempo_fixture_missing_total" in missing[0].message


def test_metrics_catalog_flags_unknown_label_and_spares_twins(bad_pkg):
    from tempo_tpu.analysis.metrics_catalog import MetricsCatalogChecker

    findings = MetricsCatalogChecker(
        catalog=_FIXTURE_METRIC_CATALOG).check(bad_pkg)
    labels = [f for f in findings if f.key.startswith("unknown-label:")]
    assert len(labels) == 1, [f.message for f in findings]
    assert "'shard'" in labels[0].message
    # the clean twin (catalogued label only) and the dynamic
    # **expansion (not statically checkable) stay silent
    lines = {f.line for f in labels}
    src = bad_pkg.by_rel["analysis_bad/metrics_drift.py"].source
    for needle in ("good_metric.inc(tenant=\"t1\")",
                   "good_metric.inc(**labels)"):
        ok_line = src[:src.index(needle)].count("\n") + 1
        assert ok_line not in lines


def test_metrics_catalog_parses_doc_tables():
    from tempo_tpu.analysis.metrics_catalog import parse_doc_catalog

    doc = (
        "| name | type | labels | meaning |\n"
        "|---|---|---|---|\n"
        "| `tempo_a_total` | counter | `tenant`, `reason` | things |\n"
        "| `tempo_b` | gauge | — | a gauge |\n"
        "| `stage` | other | `x` | not a metric row (bad type) |\n"
        "| unticked | counter | `x` | not a metric row (no ticks) |\n")
    cat = parse_doc_catalog(doc)
    assert cat == {"tempo_a_total": frozenset({"tenant", "reason"}),
                   "tempo_b": frozenset()}


def test_metrics_catalog_clean_on_real_package(real_pkg):
    """Every registered metric has a docs/observability.md row and every
    literal write-site label is catalogued — the satellite contract."""
    from tempo_tpu.analysis.metrics_catalog import MetricsCatalogChecker

    assert MetricsCatalogChecker().check(real_pkg) == []


# ------------------------------------------------- allowlist semantics


def test_stale_allowlist_entry_fails_suite(bad_pkg, tmp_path):
    p = tmp_path / "allow.toml"
    p.write_text(
        '[[allow]]\n'
        'fingerprint = "lock-order:nowhere.py:000000000000"\n'
        'justification = "this defect was fixed long ago"\n')
    report = run_suite(bad_pkg, [LockOrderChecker()], load_allowlist(str(p)))
    assert len(report.stale) == 1
    assert report.exit_code == 1
    assert "matches no current finding" in report.stale[0].message


def test_allowlisted_finding_is_split_out(bad_pkg, tmp_path):
    findings = LockOrderChecker().check(bad_pkg)
    fp = next(f for f in findings
              if f.key.startswith("blocking:")).fingerprint
    p = tmp_path / "allow.toml"
    p.write_text(
        f'[[allow]]\nfingerprint = "{fp}"\n'
        'justification = "fixture: exercised by the self-tests"\n')
    report = run_suite(bad_pkg, [LockOrderChecker()], load_allowlist(str(p)))
    assert not report.stale
    assert len(report.allowlisted) == 1
    assert all(f.fingerprint != fp for f in report.findings)


def test_allowlist_requires_justification(tmp_path):
    with pytest.raises(AllowlistError):
        _parse_subset('[[allow]]\nfingerprint = "x:y:z"\n', "t")
    with pytest.raises(AllowlistError):
        _parse_subset('[[allow]]\nfingerprint = "x:y:z"\n'
                      'justification = ""\n', "t")


def test_fingerprint_survives_line_drift():
    a = Finding(checker="c", path="p.py", line=10, message="m",
                key="blocking:f:lock:.result")
    b = Finding(checker="c", path="p.py", line=99, message="m2",
                key="blocking:f:lock:.result")
    assert a.fingerprint == b.fingerprint
    c = Finding(checker="c", path="p.py", line=10, message="m",
                key="blocking:g:lock:.result")
    assert a.fingerprint != c.fingerprint


# ------------------------------------------------- CLI / CI surface


def test_check_cli_clean_exit_zero(capsys):
    sys.path.insert(0, os.path.join(_ROOT, "scripts"))
    try:
        import check
    finally:
        sys.path.pop(0)
    rc = check.main([])
    assert rc == 0
    assert "0 finding(s)" in capsys.readouterr().out


def test_check_cli_json_and_failure_exit(capsys, tmp_path):
    sys.path.insert(0, os.path.join(_ROOT, "scripts"))
    try:
        import check
    finally:
        sys.path.pop(0)
    bad = os.path.join(_FIXTURES, "analysis_bad")
    rc = check.main([bad, "--json", "--allowlist", "none",
                     "--checker", "lock-order"])
    out = capsys.readouterr().out
    assert rc == 1
    doc = json.loads(out)
    assert doc["ok"] is False
    # 2 blocking + 1 reacquire + 1 cycle (SCC) over the lock fixtures
    assert len(doc["findings"]) == 4
    f0 = doc["findings"][0]
    assert set(f0) == {"checker", "path", "line", "message", "hint",
                       "fingerprint"}
    # usage errors are exit 2, not 1 (CI must tell them apart)
    assert check.main(["/no/such/dir"]) == 2
    assert check.main(["--checker", "no-such-checker"]) == 2


# ------------------------------------------------- mypy strict subset


def test_mypy_strict_core_subset():
    """mypy --strict over the annotated core (robustness/, utils/,
    observability/metrics.py) using the pyproject [tool.mypy] block.
    Skipped when mypy isn't installed — the container bakes no new
    dependencies, but the config + annotations ship regardless."""
    pytest.importorskip("mypy")
    out = subprocess.run(
        [sys.executable, "-m", "mypy", "--config-file",
         os.path.join(_ROOT, "pyproject.toml")],
        cwd=_ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, f"mypy --strict failed:\n{out.stdout}"
