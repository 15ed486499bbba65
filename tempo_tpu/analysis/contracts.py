"""Noop-contract checker: "knob off = one attribute read, byte-identical
output" — enforced statically.

Every observability/robustness layer in this codebase carries the same
contract: with its gate knob off, the hot path pays ONE attribute read
and nothing else — no clock read, no lock acquire, no metric write, no
allocation-heavy record protocol. Bench asserts the <2% overhead
dynamically; this checker pins the SHAPE that makes it true:

``gated-function`` rules
    a function that IS the gate (``profile.dispatch``,
    ``query_stats.begin``, ``breaker.allow_device`` ...) must test its
    gate expression before any clock read, lock acquire, or metric
    write. Work placed before the gate runs on the disabled path too —
    exactly the drift the contract forbids.

``guarded-call`` rules
    a record-protocol call (``FAULTS.hit``, ``TELEMETRY.record_*``,
    ``self.coalescer.submit``) must be dominated by its gate test —
    either lexically inside an ``if`` mentioning the gate, or after an
    early-return gate in an enclosing block. Call sites gate so the
    disarmed steady state never even enters the registry.

Both registries are data (:data:`GATED_FUNCTIONS`,
:data:`GUARDED_CALLS`): a new knob is one declaration, and the fixture
self-tests construct the checker with their own registries.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass

from .core import Checker, Finding, Package

_CLOCK_ATTRS = {"time", "perf_counter", "monotonic", "process_time",
                "thread_time"}
_METRIC_WRITE_ATTRS = {"inc", "observe", "set"}


@dataclass(frozen=True)
class GatedFunction:
    """``qualname`` in ``module`` must test ``gate_attrs`` (any of them)
    before clock/lock/metric work. ``knob`` names the config knob the
    gate implements — it appears in the finding so the operator-facing
    contract is traceable."""

    module: str             # dotted, e.g. tempo_tpu.observability.profile
    qualname: str           # e.g. DispatchProfiler.dispatch
    gate_attrs: tuple       # attr names that constitute the gate test
    knob: str


@dataclass(frozen=True)
class GuardedCall:
    """Calls ``<receiver>.<method>`` (method exact or a listed prefix)
    must be dominated by a test mentioning ``guard_attr`` (on any
    receiver — the idiom is one singleton, but ``self.x is not None``
    guards match through ``guard_name``)."""

    receiver: str           # terminal name of the receiver, e.g. FAULTS
    methods: tuple          # exact names
    method_prefixes: tuple  # prefixes, e.g. ("record_",)
    guard_attr: str         # e.g. "active", "enabled"
    guard_name: str         # name whose mention in a test also guards
    knob: str


GATED_FUNCTIONS = (
    GatedFunction("tempo_tpu.observability.profile",
                  "DispatchProfiler.dispatch", ("enabled",),
                  "search_profiling_enabled"),
    GatedFunction("tempo_tpu.observability.profile",
                  "DispatchProfiler.observe_stage", ("enabled",),
                  "search_profiling_enabled"),
    GatedFunction("tempo_tpu.search.query_stats", "begin", ("enabled",),
                  "search_query_stats_enabled"),
    GatedFunction("tempo_tpu.robustness.breaker",
                  "CircuitBreaker.allow_device", ("enabled", "_state"),
                  "search_breaker_enabled"),
    GatedFunction("tempo_tpu.robustness.breaker",
                  "CircuitBreaker.record_success", ("enabled", "_state"),
                  "search_breaker_enabled"),
    GatedFunction("tempo_tpu.robustness.dispatch", "DispatchGuard.run",
                  ("enabled", "active"), "search_breaker_enabled"),
    # owner-routed HBM: every placement lookup is internally gated, so
    # ownership disabled costs one attribute read wherever it is
    # consulted (the batcher additionally guards its call sites — see
    # the OWNERSHIP guarded-call rule below)
    GatedFunction("tempo_tpu.search.ownership", "OwnershipMap.owns_group",
                  ("enabled",), "search_hbm_ownership_enabled"),
    GatedFunction("tempo_tpu.search.ownership", "OwnershipMap.owns_block",
                  ("enabled",), "search_hbm_ownership_enabled"),
    GatedFunction("tempo_tpu.search.ownership",
                  "OwnershipMap.owner_index", ("enabled",),
                  "search_hbm_ownership_enabled"),
    # heat-adaptive replication: with rf <= 1 the heat table never
    # records (no clock read, no lock), replica lookups return empty
    # after one attribute read, and the demotion sweep is a no-op —
    # rf=1 placement stays bit for bit the single-owner behavior
    GatedFunction("tempo_tpu.search.ownership",
                  "OwnershipMap.record_access", ("replicated",),
                  "search_hbm_ownership_hot_rate"),
    GatedFunction("tempo_tpu.search.ownership",
                  "OwnershipMap.replica_indices", ("replicated",),
                  "search_hbm_ownership_rf"),
    GatedFunction("tempo_tpu.search.ownership",
                  "OwnershipMap.replicas_of", ("replicated",),
                  "search_hbm_ownership_rf"),
    GatedFunction("tempo_tpu.search.ownership",
                  "OwnershipMap.sweep", ("replicated",),
                  "search_hbm_ownership_hot_rate"),
    GatedFunction("tempo_tpu.search.ownership",
                  "OwnershipMap.is_replica", ("enabled",),
                  "search_hbm_ownership_enabled"),
    # hedged dispatch: the disarmed timer (rf <= 1) must not read a
    # clock, take its lock, or update the Jacobson/Karels estimate —
    # one attribute read per call site
    GatedFunction("tempo_tpu.search.ownership", "HedgeTimer.observe",
                  ("armed",), "search_hbm_ownership_rf"),
    GatedFunction("tempo_tpu.search.ownership", "HedgeTimer.delay_s",
                  ("armed",), "search_hedge_delay_ms"),
    GatedFunction("tempo_tpu.search.ownership", "HedgeTimer._on_stage",
                  ("armed",), "search_hbm_ownership_rf"),
    # packed HBM residency: width planning and mask packing are the
    # gate functions — disabled staging pays one attribute read and
    # keeps the byte-identical legacy layout
    GatedFunction("tempo_tpu.search.packing",
                  "PackedResidency.plan_widths", ("enabled",),
                  "search_packed_residency"),
    GatedFunction("tempo_tpu.search.packing",
                  "PackedResidency.pack_hits", ("enabled",),
                  "search_packed_residency"),
    # structural query engine: the per-request gate — disabled search
    # paths pay one attribute read and return None before any tag get,
    # parse, or cache touch
    GatedFunction("tempo_tpu.search.structural", "structural_query",
                  ("enabled",), "search_structural_enabled"),
    # plan-shape query stacking: the coalescer's grouping gate — with
    # stacking off, a structural submit reads one attribute and takes
    # the solo-flush path, never computing a group key
    GatedFunction("tempo_tpu.search.structural",
                  "StructuralGate.stack_group_key", ("stack_enabled",),
                  "search_structural_stack_enabled"),
    # segment-aligned span sharding: the placement-time reshard gate —
    # off means one attribute read and the byte-identical replicated
    # span layout at every staging site
    GatedFunction("tempo_tpu.search.structural",
                  "StructuralGate.shard_span_segment", ("shard_spans",),
                  "search_structural_shard_spans"),
    # shape-bucketed cross-plan stacking: the canonicalization gate —
    # off means one attribute read and stack_group_key keeps the
    # byte-identical exact-plan grouping
    GatedFunction("tempo_tpu.search.structural",
                  "StructuralGate.bucket_group_key", ("bucket_enabled",),
                  "search_structural_bucket_enabled"),
    # remainder-shard mesh layout: the staging pad gate — off means one
    # attribute read and the pow2 page-axis layout exactly as before
    GatedFunction("tempo_tpu.search.structural",
                  "StructuralGate.remainder_pad", ("remainder_pages",),
                  "search_structural_remainder_pages"),
    # hot-tier live search: every ingest/search/poll hook is internally
    # gated — disabled deployments pay one attribute read per push, per
    # cut, per search leg, and the legacy per-entry walk stays
    # byte-identical (tests/test_live_tier.py asserts the identity)
    GatedFunction("tempo_tpu.search.live_tier", "LiveTier.absorb",
                  ("enabled",), "search_live_tier_enabled"),
    GatedFunction("tempo_tpu.search.live_tier", "LiveTier.mark_cut",
                  ("enabled",), "search_live_tier_enabled"),
    GatedFunction("tempo_tpu.search.live_tier", "LiveTier.drop_tenant",
                  ("enabled",), "search_live_tier_enabled"),
    GatedFunction("tempo_tpu.search.live_tier",
                  "LiveTier.mark_poll_visible", ("enabled",),
                  "search_live_tier_enabled"),
    GatedFunction("tempo_tpu.search.live_tier", "LiveTier.poll_visible",
                  ("enabled",), "search_live_tier_enabled"),
    GatedFunction("tempo_tpu.search.live_tier", "LiveTier.search",
                  ("enabled",), "search_live_tier_enabled"),
    GatedFunction("tempo_tpu.search.live_tier", "LiveTier.subscribe",
                  ("enabled",), "search_live_tier_enabled"),
    GatedFunction("tempo_tpu.search.live_tier", "LiveTier.unsubscribe",
                  ("enabled",), "search_live_tier_enabled"),
    GatedFunction("tempo_tpu.search.live_tier",
                  "LiveTier.has_subscribers", ("enabled",),
                  "search_live_tier_enabled"),
    GatedFunction("tempo_tpu.search.live_tier", "LiveTier.notify_push",
                  ("enabled",), "search_live_tier_enabled"),
    # device-side aggregate analytics: the ingest hook gates first —
    # the default-off deployment's push-ack path pays one attribute
    # read before any blob decode, clock read, or planner touch
    GatedFunction("tempo_tpu.search.analytics",
                  "AnalyticsEngine.consume_blob", ("enabled",),
                  "search_analytics_enabled"),
    # dogfood self-ingest: query-stat annotation only runs when
    # self-traces actually flow into the `_selftrace` tenant — the
    # default-off deployment pays one attribute read before any tracer
    # lookup or clock read
    GatedFunction("tempo_tpu.observability.selftrace",
                  "SelfTraceGate.annotate_query", ("ingest_enabled",),
                  "selftrace_ingest_enabled"),
    # anomaly flight recorder: a disabled recorder must not snapshot
    # subsystems, read clocks, or take its lock when a trigger fires
    GatedFunction("tempo_tpu.observability.flightrecorder",
                  "FlightRecorder.record", ("enabled",),
                  "selftrace_ingest_enabled"),
)

GUARDED_CALLS = (
    GuardedCall("FAULTS", ("hit",), (), "active", "FAULTS",
                "robustness_faults"),
    GuardedCall("TELEMETRY", ("set_queue_state",), ("record_",),
                "enabled", "TELEMETRY", "ingest_telemetry_enabled"),
    GuardedCall("coalescer", ("submit",), (), "coalescer", "coalescer",
                "search_coalesce_max_queries"),
    # hot-path ownership lookups must be dominated by the one-attribute
    # gate read — the disabled serving path never enters the map (the
    # heat-table feed rides the same gate: record_access additionally
    # self-gates on `replicated`, so rf=1 deployments pay one read)
    GuardedCall("OWNERSHIP", ("owns_group", "record_access"), (),
                "enabled", "OWNERSHIP", "search_hbm_ownership_enabled"),
    # hedge-timer touches (the delay derivation reads a lock +
    # estimator state, observe() reads the clock's output) only behind
    # the armed flag: with search_hbm_ownership_rf <= 1 no call site
    # may reach the timer — no clock read, no lock, no thread spawn
    GuardedCall("HEDGE", ("observe", "delay_s"), (), "armed", "HEDGE",
                "search_hbm_ownership_rf"),
    # staging-site packing calls likewise: the disabled path must not
    # even compute the width-planner inputs (duration rollup maxes)
    GuardedCall("PACKING", ("plan_widths", "pack_hits"), (), "enabled",
                "PACKING", "search_packed_residency"),
    # structural span staging: the disabled path must not even inspect
    # blocks for span segments, let alone stack/pad/upload them
    GuardedCall("STRUCTURAL", ("stack_spans",), (),
                "enabled", "STRUCTURAL", "search_structural_enabled"),
    # plan-shape stacking: group-key computation only behind the
    # stacking gate — a disabled coalescer submit stays on the exact
    # solo-flush path
    GuardedCall("STRUCTURAL", ("stack_group_key",), (), "stack_enabled",
                "STRUCTURAL", "search_structural_stack_enabled"),
    # span-sharding: the reshard (an O(spans) numpy pass) only behind
    # its gate — disabled staging keeps the replicated layout untouched
    GuardedCall("STRUCTURAL", ("shard_span_segment",), (), "shard_spans",
                "STRUCTURAL", "search_structural_shard_spans"),
    # remainder-shard staging: the minimal-multiple pad computation
    # only behind its gate — disabled staging keeps the pow2 layout
    # without even calling the pad helper
    GuardedCall("STRUCTURAL", ("remainder_pad",), (), "remainder_pages",
                "STRUCTURAL", "search_structural_remainder_pages"),
    # hot-tier hooks on the ingest/search hot paths: every call site
    # must be dominated by the one-attribute gate read so the disabled
    # deployment never enters the tier (poll_visible/has_subscribers
    # are consulted inside guard tests themselves and stay covered by
    # their internal gates)
    GuardedCall("LIVE_TIER", ("absorb", "mark_cut", "search",
                              "mark_poll_visible", "subscribe",
                              "unsubscribe", "notify_push"), (),
                "enabled", "LIVE_TIER", "search_live_tier_enabled"),
    # aggregate analytics hooks: the ingest feed and the query-side
    # batch staging both only behind the one-attribute gate read (the
    # batcher folds the gate into `want_agg` = enabled AND the request
    # opted in — mentioning it in a test guards like the gate itself)
    GuardedCall("ANALYTICS", ("consume_blob", "stage_for_batch"), (),
                "enabled", "want_agg", "search_analytics_enabled"),
    # dogfood hook on a hot path (query-stat publish): call sites gate
    # on the one-attribute read so the default-off deployment never
    # enters the annotation protocol
    GuardedCall("SELFTRACE", ("annotate_query",), (),
                "ingest_enabled", "SELFTRACE",
                "selftrace_ingest_enabled"),
    # served-search spans written after the fact and the device
    # timeline's watcher: with no tracer installed (`self_tracing`
    # off) a call site must not build the attributes, take the launch
    # id or start the watcher thread — every site is dominated by a
    # `span.recording` or `tracing.get_tracer() is not None` test
    GuardedCall("tracing", ("record_span",), (), "recording",
                "get_tracer", "self_tracing"),
    GuardedCall("DEVICE_TIMELINE", ("watch",), (), "recording",
                "get_tracer", "self_tracing"),
    # the thread's CPU clock is read only where a span will be written:
    # a stamp site is `c0 = tracing.cpu_ns() if span.recording else None`,
    # one test of a flag it already holds; the profiler's stage timers
    # hold theirs as `rec.intervals is not None` (kept only while a
    # tracer is installed)
    GuardedCall("tracing", ("cpu_ns",), (), "recording",
                "get_tracer", "self_tracing"),
    GuardedCall("tracing", ("cpu_ns",), (), "intervals",
                "intervals", "self_tracing"),
    # flight-recorder triggers (breaker trip, watchdog, slow query)
    # live on failure paths of otherwise-hot code: each site reads
    # RECORDER.enabled before snapshotting state into a bundle
    GuardedCall("RECORDER", ("record",), (), "enabled", "RECORDER",
                "selftrace_ingest_enabled"),
)


def _mention_polarities(test: ast.AST, rule: GuardedCall) -> set:
    """Which polarities the gate mention appears in: "positive" means
    the test is truthy when the gate is ON (`if X.active:`,
    `if x is not None:`), "negated" means truthy when it is OFF
    (`if not X.active:`, `if x is None:`). An early-exit `if` guards
    its remaining siblings only in the NEGATED polarity — `if
    FAULTS.active: return` exits on the ARMED path and leaves the
    disabled path running straight into the record call. Likewise the
    `orelse` branch of a gate test is the OPPOSITE polarity of its
    body."""

    def is_mention(node: ast.AST) -> bool:
        return (isinstance(node, ast.Attribute)
                and node.attr in (rule.guard_attr, rule.guard_name)) \
            or (isinstance(node, ast.Name) and node.id == rule.guard_name)

    out: set = set()

    def walk(node: ast.AST, negated: bool) -> None:
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
            walk(node.operand, not negated)
            return
        if isinstance(node, ast.Compare) and len(node.ops) == 1 \
                and isinstance(node.comparators[0], ast.Constant) \
                and node.comparators[0].value is None:
            # `x is None` flips polarity (truth = gate ABSENT);
            # `x is not None` keeps it
            if isinstance(node.ops[0], ast.Is):
                walk(node.left, not negated)
                return
            if isinstance(node.ops[0], ast.IsNot):
                walk(node.left, negated)
                return
        if is_mention(node):
            out.add("negated" if negated else "positive")
        for c in ast.iter_child_nodes(node):
            walk(c, negated)

    walk(test, False)
    return out


def _test_mentions_negated(test: ast.AST, rule: GuardedCall) -> bool:
    return "negated" in _mention_polarities(test, rule)


def _branch_guards(test: ast.AST, rules, guards: frozenset) -> tuple:
    """(guards of the branch taken when `test` is truthy, guards of the
    other branch): polarity-aware, for an `if` statement and for a
    conditional expression alike."""
    body_g, else_g = guards, guards
    for rule in rules:
        pol = _mention_polarities(test, rule)
        if "positive" in pol:
            body_g = body_g | {rule.knob}
        if "negated" in pol:
            else_g = else_g | {rule.knob}
    return body_g, else_g


def _receiver_name(fn: ast.Attribute) -> str | None:
    """Terminal name of the receiver: FAULTS.hit -> FAULTS,
    self.coalescer.submit -> coalescer."""
    base = fn.value
    if isinstance(base, ast.Name):
        return base.id
    if isinstance(base, ast.Attribute):
        return base.attr
    return None


def _rule_matches(rule: GuardedCall, fn: ast.Attribute) -> bool:
    if _receiver_name(fn) != rule.receiver:
        return False
    if fn.attr in rule.methods:
        return True
    return any(fn.attr.startswith(p) for p in rule.method_prefixes)


class NoopContractChecker(Checker):
    id = "noop-contract"

    def __init__(self, gated=GATED_FUNCTIONS, guarded=GUARDED_CALLS):
        self.gated = tuple(gated)
        self.guarded = tuple(guarded)

    def check(self, pkg: Package) -> list[Finding]:
        findings: list[Finding] = []
        by_key = {}
        for mod, qual, node in pkg.functions():
            by_key[(mod.dotted, qual)] = (mod, node)
        for rule in self.gated:
            hit = by_key.get((rule.module, rule.qualname))
            if hit is None:
                findings.append(Finding(
                    checker=self.id, path=rule.module.replace(".", "/")
                    + ".py", line=1,
                    message=(f"gate registry names {rule.module}."
                             f"{rule.qualname} but no such function "
                             "exists — the registry drifted from the "
                             "code"),
                    hint="update GATED_FUNCTIONS in "
                         "tempo_tpu/analysis/contracts.py",
                    key=f"gate-missing:{rule.module}.{rule.qualname}"))
                continue
            mod, node = hit
            findings.extend(self._check_gated(rule, mod, node))
        # guarded-call domination is checked package-wide (the rules
        # match by receiver shape, not by symbol table)
        for mod, qual, fnode in pkg.functions():
            findings.extend(self._check_guarded(mod, qual, fnode))
        return findings

    # ---- gated functions ----

    def _check_gated(self, rule: GatedFunction, mod, func) -> list:
        findings = []
        gate_line = None
        pre_gate: list = []

        def is_gate_test(test: ast.AST) -> bool:
            for node in ast.walk(test):
                if isinstance(node, ast.Attribute) \
                        and node.attr in rule.gate_attrs:
                    return True
                if isinstance(node, ast.Name) \
                        and node.id in rule.gate_attrs:
                    return True
            return False

        # lexical scan over the TOP-LEVEL body: the gate idiom is an
        # early `if not <gate>: return ...` (or a gated return); every
        # registered function follows it, and anything before that
        # statement runs on the disabled path
        for stmt in func.body:
            if isinstance(stmt, ast.If) and is_gate_test(stmt.test):
                gate_line = stmt.lineno
                break
            if isinstance(stmt, ast.Return) and stmt.value is not None \
                    and is_gate_test(stmt.value):
                # `return X if gated else noop` boolean-gate forms
                gate_line = stmt.lineno
                break
            pre_gate.append(stmt)
        if gate_line is None:
            findings.append(Finding(
                checker=self.id, path=mod.rel, line=func.lineno,
                message=(f"{rule.qualname}() implements the "
                         f"{rule.knob} gate but no test of "
                         f"{'/'.join(rule.gate_attrs)} was found in it"),
                hint="gate first, or update the GATED_FUNCTIONS "
                     "registry if the gate moved",
                key=f"gate-absent:{rule.qualname}"))
            return findings
        for stmt in pre_gate:
            for why, line in _contract_work(stmt):
                findings.append(Finding(
                    checker=self.id, path=mod.rel, line=line,
                    message=(f"{rule.qualname}() does {why} BEFORE its "
                             f"{rule.knob} gate (line {gate_line}) — the "
                             "disabled path pays it on every call"),
                    hint="move it after the gate test, or justify the "
                         "exception in the allowlist",
                    key=f"pre-gate:{rule.qualname}:{why}"))
        return findings

    # ---- guarded calls ----

    def _check_guarded(self, mod, qual, func) -> list:
        findings = []

        def walk(stmts, guards: frozenset) -> None:
            g = guards
            for stmt in stmts:
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.ClassDef)):
                    continue
                # early-return gate: `if not <guard>: return/raise/...`
                # guards the remaining siblings. Polarity matters:
                # `if <guard>: return` exits on the ARMED path and the
                # disabled path keeps going — that must NOT count.
                if isinstance(stmt, ast.If) and _exits(stmt.body):
                    for rule in self.guarded:
                        if _test_mentions_negated(stmt.test, rule):
                            g = g | {rule.knob}
                if isinstance(stmt, ast.If):
                    # polarity-aware: the body is guarded when the test
                    # is truthy-with-gate-ON, the else branch when it is
                    # truthy-with-gate-OFF — `if X.active: ... else:
                    # X.hit()` runs the record protocol exactly on the
                    # disabled path and must NOT get guard credit
                    body_g, else_g = _branch_guards(stmt.test,
                                                    self.guarded, g)
                    walk(stmt.body, body_g)
                    walk(stmt.orelse, else_g)
                elif isinstance(stmt, (ast.For, ast.While, ast.With,
                                       ast.AsyncFor, ast.AsyncWith)):
                    walk(stmt.body, g)
                    walk(getattr(stmt, "orelse", []), g)
                elif isinstance(stmt, ast.Try):
                    walk(stmt.body, g)
                    for h in stmt.handlers:
                        walk(h.body, g)
                    walk(stmt.orelse, g)
                    walk(stmt.finalbody, g)
                self._scan_calls(stmt, g, mod, qual, findings)
            return

        walk(func.body, frozenset())
        return findings

    def _scan_calls(self, stmt, guards, mod, qual, findings) -> None:
        if isinstance(stmt, (ast.If, ast.For, ast.While, ast.With,
                             ast.Try, ast.AsyncFor, ast.AsyncWith)):
            # compound statements: their test/iter/with-item expressions
            # are at this guard level; bodies were walked with inner
            # guards. With-items matter: `with TELEMETRY.record_x():`
            # is a record-protocol call too
            exprs = [getattr(stmt, "test", None),
                     getattr(stmt, "iter", None)]
            exprs += [item.context_expr
                      for item in getattr(stmt, "items", [])]
            roots = [e for e in exprs if e is not None]
        else:
            roots = [stmt]
        # (node, the guards that dominate it): a conditional expression
        # `X if <guard> else Y` guards X as an `if` guards its body and
        # Y as its else branch
        todo = [(r, guards) for r in roots]
        while todo:
            node, g = todo.pop()
            if isinstance(node, ast.IfExp):
                body_g, else_g = _branch_guards(node.test, self.guarded, g)
                todo += [(node.test, g), (node.body, body_g),
                         (node.orelse, else_g)]
                continue
            todo += [(c, g) for c in ast.iter_child_nodes(node)]
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            for rule in self.guarded:
                if not _rule_matches(rule, node.func):
                    continue
                if rule.knob in g:
                    continue
                findings.append(Finding(
                    checker=self.id, path=mod.rel, line=node.lineno,
                    message=(f"{qual}() calls {rule.receiver}."
                             f"{node.func.attr}() without a dominating "
                             f"{rule.guard_name}.{rule.guard_attr} "
                             f"check — the {rule.knob}=off path enters "
                             "the record protocol"),
                    hint=f"wrap the call in `if {rule.guard_name}."
                         f"{rule.guard_attr}:` (the one-attribute-read "
                         "idiom every other site uses)",
                    key=f"unguarded:{qual}:{rule.receiver}."
                        f"{node.func.attr}"))


def _exits(body: list) -> bool:
    return bool(body) and isinstance(
        body[-1], (ast.Return, ast.Raise, ast.Continue, ast.Break))


def _contract_work(stmt: ast.stmt):
    """(description, line) for clock reads, lock acquires and metric
    writes inside one pre-gate statement."""
    for node in ast.walk(stmt):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute):
            fn = node.func
            if fn.attr in _CLOCK_ATTRS and isinstance(fn.value, ast.Name) \
                    and fn.value.id in ("time", "_time"):
                yield f"a clock read (time.{fn.attr}())", node.lineno
            elif fn.attr == "acquire":
                yield "a lock acquire", node.lineno
            elif fn.attr in _METRIC_WRITE_ATTRS \
                    and isinstance(fn.value, ast.Attribute) \
                    and isinstance(fn.value.value, ast.Name) \
                    and fn.value.value.id in ("obs", "metrics"):
                yield (f"a metric write (obs.{fn.value.attr}."
                       f"{fn.attr}())"), node.lineno
        if isinstance(node, ast.With):
            for item in node.items:
                ctx = item.context_expr
                if isinstance(ctx, ast.Attribute) \
                        and ctx.attr.endswith("lock"):
                    yield "a lock acquire (with ...lock)", node.lineno
