"""Per-query execution inspector: what did THIS query cost, and whose
device time was it?

PR 5 profiles every device *dispatch* and PR 6 turned those aggregates
into offload policy, but a coalesced Q-way dispatch serves N queries
from M tenants and all of its execute/h2d/compile time lands in
anonymous process aggregates. This module threads a ``QueryStats``
context through the whole search path — api/http → frontend fan-out →
querier → TempoDB → batcher/coalescer/engines → planner/dict probe —
so every request accumulates:

  - blocks scanned vs skipped, with the skip REASON (time-range,
    duration rollup, dictionary prune, meta window);
  - bytes inspected split host vs device (device kernels vs fallback
    proto scans + host dictionary probes);
  - staging-cache behavior as THIS query saw it (HBM hit vs re-stage,
    host-tier hit, probe-dict staging);
  - planner decisions taken while compiling it (target + predicted ms);
  - per-stage device-seconds attributed from its dispatches. A fused
    coalesced dispatch apportions each stage across its member queries
    by their padded predicate-table rows, with a conservation
    invariant: the attributed shares sum exactly to the dispatch total
    (the last member takes the float remainder).

Surfaces:

  - opt-in explain (``?explain=1`` / ``X-Tempo-Explain`` → SearchRequest
    .explain): the full breakdown rides SearchResponse.metrics
    .query_stats_json across process boundaries and the HTTP layer
    inlines it as a JSON object;
  - a structured slow-query log: one rate-limited JSON line per query
    slower than ``search_slow_query_log_s`` (tenant, self-trace id,
    complete stats);
  - ``/debug/querystats``: recent ring + per-tenant aggregates + top-K
    by device-seconds and by bytes;
  - per-tenant accounting metrics
    ``tempo_search_query_device_seconds_total{tenant}``,
    ``tempo_search_query_bytes_inspected_total{tenant,placement}`` and
    the ``tempo_search_query_stage_seconds{stage}`` histogram (whose
    OpenMetrics exemplars link buckets to self-traces, the PR 5
    plumbing).

Noop contract (same stance as the dispatch profiler):
``search_query_stats_enabled: false`` creates no QueryStats at all —
call sites read one contextvar, get ``None``, and branch out; results
are byte-identical either way (bench phase ``query_stats_overhead``
asserts the record protocol stays under 2% of a dispatch).

Scopes: the execution layer (TempoDB.search / search_block /
search_blocks — the querier processes, where kernels actually run)
books scope="exec" stats, which feed the per-tenant counters and
tenant aggregates; the frontend books one scope="request" entry per
external request (merged from its sub-responses) for the ring and the
slow-query log, WITHOUT re-booking counters — in single-binary mode
both layers share this registry and double counting would follow.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import threading
import time
from collections import deque

from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability.flightrecorder import (RECORDER,
                                                    TRIGGER_SLOW_QUERY)
from tempo_tpu.observability.log import TenantTokenBucket, get_logger
from tempo_tpu.observability.selftrace import SELFTRACE

log = get_logger("tempo_tpu.querystats")
slow_log = get_logger("tempo_tpu.slowquery")

_ACTIVE: contextvars.ContextVar = contextvars.ContextVar(
    "tempo_query_stats", default=None)
# True on threads executing sub-requests FOR an in-process frontend
# (QueryFrontend wraps its worker-pool jobs in fronted()): exec-scope
# records born there suppress their own slow-query log line — the
# frontend's request-scope line covers the query, and two lines per
# offender would halve the limiter's effective rate
_FRONTED: contextvars.ContextVar = contextvars.ContextVar(
    "tempo_query_fronted", default=False)

_TOP_K = 10  # per-ranking entries kept for /debug/querystats


class QueryStats:
    """One query's accumulating execution record. Thread-safe: fused
    dispatch attribution arrives from coalescer flush threads while the
    owning search thread keeps draining."""

    __slots__ = ("tenant", "scope", "query", "trace_id",
                 "t0", "wall_s", "blocks_inspected", "skipped",
                 "bytes_host", "bytes_device", "cache", "stages",
                 "device_stages", "h2d_bytes", "dispatches",
                 "fused_dispatches", "coalesced_with", "planner",
                 "host_probe", "subqueries", "fronted",
                 "staged_physical", "staged_logical", "structural",
                 "_lock")

    def __init__(self, tenant: str, scope: str = "exec",
                 query: dict | None = None):
        from tempo_tpu.observability import tracing

        self.tenant = tenant
        self.scope = scope
        self.query = query or {}
        span = tracing.current_span()
        self.trace_id = (span.context.trace_id.hex()
                         if span.recording else None)
        self.t0 = time.perf_counter()
        self.wall_s = 0.0
        self.blocks_inspected = 0
        self.skipped: dict[str, int] = {}
        self.bytes_host = 0
        self.bytes_device = 0
        self.cache: dict[str, int] = {}
        self.stages: dict[str, float] = {}        # host-side wall stages
        self.device_stages: dict[str, float] = {}  # attributed dispatch
        self.h2d_bytes = 0                         # attributed h2d share
        self.dispatches = 0
        self.fused_dispatches = 0
        self.coalesced_with = 0   # peer queries sharing my dispatches
        self.planner = {"host": 0, "device": 0, "predicted_ms": 0.0}
        self.host_probe = {"count": 0, "seconds": 0.0, "bytes": 0}
        # staged bytes this query's scans read, both sides of the
        # packed-residency split (search/packing.py): physical = bytes
        # as resident (packed), logical = the unpacked equivalent
        self.staged_physical = 0
        self.staged_logical = 0
        # structural plan registration (search/structural.py): node id
        # -> {op, detail, est_bytes} accumulated across this query's
        # compiled groups; to_dict() apportions the measured device
        # execute seconds over the byte weights (one fused kernel has no
        # per-node timer — the conserved split follows the same per-byte
        # model the planner calibrates)
        self.structural: dict | None = None
        self.subqueries = 0       # request scope: sub-responses merged
        self.fronted = _FRONTED.get()
        self._lock = threading.Lock()

    # ---- recording (each O(1), called per group / per dispatch) ----

    def add_skip(self, reason: str, n: int = 1) -> None:
        with self._lock:
            self.skipped[reason] = self.skipped.get(reason, 0) + n

    def add_inspected(self, blocks: int = 0, nbytes: int = 0,
                      placement: str = "device") -> None:
        with self._lock:
            self.blocks_inspected += blocks
            if placement == "device":
                self.bytes_device += nbytes
            else:
                self.bytes_host += nbytes

    def add_cache(self, event: str, n: int = 1) -> None:
        with self._lock:
            self.cache[event] = self.cache.get(event, 0) + n

    def add_stage(self, name: str, seconds: float) -> None:
        with self._lock:
            self.stages[name] = self.stages.get(name, 0.0) + seconds

    def add_device_stages(self, stages: dict, h2d_bytes: float = 0,
                          fused_q: int = 1, count: bool = True) -> None:
        """Fold one dispatch's (possibly apportioned) stage share in.
        `fused_q`: how many real queries shared the launch; `count`:
        False for late additions to an already-counted dispatch (the
        drain-side d2h sync). Byte shares stay float so a fused
        dispatch's apportioned bytes conserve to float tolerance."""
        with self._lock:
            for k, v in stages.items():
                self.device_stages[k] = self.device_stages.get(k, 0.0) + v
            self.h2d_bytes += h2d_bytes
            if count:
                self.dispatches += 1
                if fused_q > 1:
                    self.fused_dispatches += 1
                    self.coalesced_with += fused_q - 1

    def add_planner(self, target: str, predicted_s: float) -> None:
        with self._lock:
            self.planner[target] = self.planner.get(target, 0) + 1
            self.planner["predicted_ms"] += predicted_s * 1e3

    def add_staged(self, physical: int, logical: int) -> None:
        """Staged bytes one group's scan read — the bytes-inspected
        physical/logical split the explain breakdown reports."""
        with self._lock:
            self.staged_physical += int(physical)
            self.staged_logical += int(logical)

    def add_host_probe(self, seconds: float, nbytes: int) -> None:
        with self._lock:
            self.host_probe["count"] += 1
            self.host_probe["seconds"] += seconds
            self.host_probe["bytes"] += nbytes

    def add_structural(self, compiled) -> None:
        """Register a compiled structural plan (one per scanned group;
        plans are identical across a query's groups, byte weights sum)."""
        with self._lock:
            if self.structural is None:
                self.structural = {}
            for nid, op, detail in compiled.node_info:
                node = self.structural.get(nid)
                if node is None:
                    node = self.structural[nid] = {
                        "op": op, "detail": detail, "est_bytes": 0}
                node["est_bytes"] += int(compiled.node_bytes.get(nid, 0))

    # ---- derived ----

    @property
    def device_seconds(self) -> float:
        with self._lock:
            return sum(self.device_stages.values())

    def absorb_metrics(self, m) -> None:
        """Request-scope fill from merged proto SearchMetrics when no
        explain breakdowns travelled (explain off): totals only — the
        stage split lives with the executors."""
        with self._lock:
            self.blocks_inspected += int(m.inspected_blocks)
            dev = int(m.inspected_bytes_device)
            self.bytes_device += dev
            self.bytes_host += max(0, int(m.inspected_bytes) - dev)
            if m.device_seconds:
                self.device_stages["total"] = \
                    self.device_stages.get("total", 0.0) + m.device_seconds
            if m.skipped_blocks:
                self.skipped["all"] = \
                    self.skipped.get("all", 0) + int(m.skipped_blocks)

    def merge_child(self, child: dict) -> None:
        """Fold a sub-response's explain dict into a request-scope
        record (numeric leaves sum; the frontend's merge path)."""
        with self._lock:
            self.subqueries += 1
            self.blocks_inspected += int(child.get("blocks_inspected", 0))
            b = child.get("bytes_inspected") or {}
            self.bytes_host += int(b.get("host", 0))
            self.bytes_device += int(b.get("device", 0))
            self.h2d_bytes += int(child.get("h2d_bytes", 0))
            self.dispatches += int(child.get("dispatches", 0))
            self.fused_dispatches += int(child.get("fused_dispatches", 0))
            self.coalesced_with += int(child.get("coalesced_with", 0))
            for d, mine in ((child.get("skipped_blocks"), self.skipped),
                            (child.get("cache"), self.cache)):
                for k, v in (d or {}).items():
                    mine[k] = mine.get(k, 0) + v
            for d, mine in ((child.get("stages_ms"), self.stages),
                            (child.get("device_stages_ms"),
                             self.device_stages)):
                for k, v in (d or {}).items():
                    mine[k] = mine.get(k, 0.0) + v / 1e3
            sb = child.get("staged_bytes") or {}
            self.staged_physical += int(sb.get("physical", 0))
            self.staged_logical += int(sb.get("logical", 0))
            for k, v in (child.get("planner") or {}).items():
                self.planner[k] = self.planner.get(k, 0) + v
            hp = child.get("host_probe") or {}
            self.host_probe["count"] += int(hp.get("count", 0))
            self.host_probe["seconds"] += float(hp.get("ms", 0.0)) / 1e3
            self.host_probe["bytes"] += int(hp.get("bytes", 0))
            sn = (child.get("structural") or {}).get("nodes")
            if sn:
                # sub-responses share one plan (node ids are preorder
                # positions in the same IR): bytes and measured shares sum
                if self.structural is None:
                    self.structural = {}
                for node in sn:
                    mine = self.structural.get(node["id"])
                    if mine is None:
                        mine = self.structural[node["id"]] = {
                            "op": node.get("op", "?"),
                            "detail": node.get("detail", ""),
                            "est_bytes": 0, "_device_ms": 0.0}
                    mine["est_bytes"] += int(node.get("est_bytes", 0))
                    mine["_device_ms"] = (mine.get("_device_ms", 0.0)
                                          + float(node.get("device_ms",
                                                           0.0)))

    def to_dict(self) -> dict:
        with self._lock:
            d = {
                "tenant": self.tenant,
                "scope": self.scope,
                "wall_ms": round((self.wall_s or
                                  (time.perf_counter() - self.t0)) * 1e3,
                                 3),
                "blocks_inspected": self.blocks_inspected,
                "skipped_blocks": dict(self.skipped),
                "bytes_inspected": {"host": self.bytes_host,
                                    "device": self.bytes_device},
                "device_seconds": round(
                    sum(self.device_stages.values()), 9),
                "device_stages_ms": {k: round(v * 1e3, 6)
                                     for k, v in
                                     self.device_stages.items()},
                "stages_ms": {k: round(v * 1e3, 3)
                              for k, v in self.stages.items()},
                "dispatches": self.dispatches,
                "fused_dispatches": self.fused_dispatches,
                "coalesced_with": self.coalesced_with,
                "h2d_bytes": int(round(self.h2d_bytes)),
                "cache": dict(self.cache),
            }
            if self.staged_physical or self.staged_logical:
                d["staged_bytes"] = {"physical": self.staged_physical,
                                     "logical": self.staged_logical}
            if self.structural:
                # compiled plan tree with per-node device-seconds:
                # measured execute time apportions over the registered
                # byte weights (conserved — shares sum to the total).
                # A first-seen shape books its time as "compile"; the
                # fallback to the stage total keeps the tree honest
                # rather than all-zero on cold dispatches.
                exec_s = (self.device_stages.get("execute")
                          or sum(self.device_stages.values()))
                total_b = max(1, sum(n["est_bytes"]
                                     for n in self.structural.values()))
                d["structural"] = {
                    "nodes": [
                        {"id": nid, "op": n["op"],
                         **({"detail": n["detail"]} if n["detail"]
                            else {}),
                         "est_bytes": n["est_bytes"],
                         # merged (request-scope) records carry their
                         # children's measured shares; exec-scope records
                         # apportion their own execute total
                         "device_ms": round(
                             n["_device_ms"] if "_device_ms" in n
                             else exec_s * (n["est_bytes"] / total_b)
                             * 1e3, 6)}
                        for nid, n in sorted(self.structural.items())
                    ],
                }
            if self.query:
                d["query"] = dict(self.query)
            if self.trace_id:
                d["trace_id"] = self.trace_id
            if self.planner["host"] or self.planner["device"]:
                d["planner"] = {k: (round(v, 3) if k == "predicted_ms"
                                    else v)
                                for k, v in self.planner.items()}
            if self.host_probe["count"]:
                d["host_probe"] = {
                    "count": self.host_probe["count"],
                    "ms": round(self.host_probe["seconds"] * 1e3, 3),
                    "bytes": self.host_probe["bytes"],
                }
            if self.subqueries:
                d["subqueries"] = self.subqueries
            return d

    def finish(self) -> dict:
        """Close the record: stamp wall time, publish to the registry
        (metrics, ring, slow log). Returns the final dict."""
        self.wall_s = time.perf_counter() - self.t0
        return REGISTRY.publish(self)


def apportion(totals: dict, weights: list) -> list[dict]:
    """Split per-stage totals across members proportionally to
    `weights`, conserving the sum: members 0..n-2 get total*w/W and the
    LAST member takes the remainder of the same running accumulation,
    so per stage adding the shares up in member order with `+` — how
    every consumer folds them (add_device_stages) — gives back `total`.
    The guarantee is about that accumulation, not about the builtin
    sum(): from Python 3.12 sum() compensates float rounding and may
    land one ulp away."""
    n = len(weights)
    if n == 1:
        return [dict(totals)]
    W = float(sum(weights)) or float(n)
    shares: list[dict] = [{} for _ in range(n)]
    for stage, total in totals.items():
        acc = 0.0
        for i in range(n - 1):
            s = total * (weights[i] / W)
            shares[i][stage] = s
            acc += s
        shares[n - 1][stage] = total - acc
    return shares


# per-tenant token buckets under a global ceiling — the slow line must
# stay pure JSON (RateLimitedLogger prefixes `tenant=...`), so the raw
# bucket class is used, not the logger wrapper. Promoted to
# observability.log so the slow-FLUSH log (ingest_telemetry) shares the
# exact limiter semantics instead of re-deriving them.
_SlowLogLimiter = TenantTokenBucket


class QueryStatsRegistry:
    """Process-wide sink (module singleton ``REGISTRY``, the PROFILER
    idiom): finished QueryStats land in a bounded ring, per-tenant
    aggregates, top-K rankings, the per-tenant counters, and — past the
    threshold — the slow-query log."""

    def __init__(self, enabled: bool = True, slow_s: float = 10.0,
                 ring_size: int = 256):
        self.enabled = enabled
        self.slow_s = slow_s
        self._ring: deque = deque(maxlen=ring_size)
        self._lock = threading.Lock()
        # tenant -> {queries, device_seconds, bytes_host, bytes_device,
        #            slow_queries}; exec scope only (see module
        # docstring — request scope would double count in-process)
        self._tenants: dict[str, dict] = {}
        self._top_device: list[tuple] = []   # (device_seconds, dict)
        self._top_bytes: list[tuple] = []    # (bytes_total, dict)
        self._limiter = _SlowLogLimiter()
        self._published = 0

    @staticmethod
    def _top_insert(top: list, key: float, d: dict) -> None:
        if key <= 0:
            return
        top.append((key, d))
        top.sort(key=lambda t: t[0], reverse=True)
        del top[_TOP_K:]

    def publish(self, qs: QueryStats) -> dict:
        # EVERYTHING below reads the locked snapshot `d`, never the
        # live QueryStats dicts: a query that early-quit on its limit
        # can still receive a late coalescer-flush attribution on the
        # window-timer thread, and iterating the live dicts here would
        # race it (dict-changed-size in the search path). Attribution
        # landing after this snapshot is dropped by design — the
        # abandoned dispatch's share has no response to ride anyway.
        d = qs.to_dict()
        if qs.scope == "request" and SELFTRACE.ingest_enabled:
            # dogfood pipeline: publish runs on the request thread, so
            # the current span IS the request-scope span — the finished
            # breakdown attaches as query.* attributes and travels into
            # _selftrace with the trace (gate off = one attribute read)
            SELFTRACE.annotate_query(d)
        dev_s = d["device_seconds"]
        b = d["bytes_inspected"]
        bytes_host, bytes_device = b["host"], b["device"]
        with self._lock:
            self._published += 1
            self._ring.append(d)
            self._top_insert(self._top_device, dev_s, d)
            self._top_insert(self._top_bytes,
                             bytes_host + bytes_device, d)
            if qs.scope == "exec":
                t = self._tenants.get(qs.tenant)
                if t is None:
                    t = self._tenants[qs.tenant] = {
                        "queries": 0, "device_seconds": 0.0,
                        "bytes_host": 0, "bytes_device": 0,
                        "slow_queries": 0}
                t["queries"] += 1
                t["device_seconds"] += dev_s
                t["bytes_host"] += bytes_host
                t["bytes_device"] += bytes_device
        if qs.scope == "exec":
            if dev_s:
                obs.query_device_seconds.inc(dev_s, tenant=qs.tenant)
            if bytes_device:
                obs.query_bytes_inspected.inc(
                    bytes_device, tenant=qs.tenant, placement="device")
            if bytes_host:
                obs.query_bytes_inspected.inc(
                    bytes_host, tenant=qs.tenant, placement="host")
            for stage, ms in d["stages_ms"].items():
                obs.query_stage_seconds.observe(ms / 1e3, stage=stage)
            for stage, ms in d["device_stages_ms"].items():
                obs.query_stage_seconds.observe(ms / 1e3,
                                                stage=f"device_{stage}")
        if self.slow_s > 0 and qs.wall_s >= self.slow_s:
            # ONE slow-query booking per query per process — counter
            # AND log use the same rule: an exec record produced UNDER
            # an in-process frontend (qs.fronted — the frontend marks
            # its worker threads) is covered by that frontend's
            # request-scope record; counting each sub-request too would
            # inflate the counter by the shard fan-out factor while the
            # log (deduped) says 1. Standalone querier processes have
            # no request scope and book their exec view.
            if qs.scope == "request" or not qs.fronted:
                obs.slow_queries.inc(tenant=qs.tenant)
                with self._lock:
                    t = self._tenants.get(qs.tenant)
                    if t is not None:
                        t["slow_queries"] += 1
                if self._limiter.allow(qs.tenant):
                    slow_log.warning("%s", json.dumps(
                        {"msg": "slow query",
                         "threshold_s": self.slow_s, **d},
                        separators=(",", ":"), sort_keys=True))
                # flight recorder: the slow query snapshots its bundle
                # with its own self-trace id, so /debug/flightrecorder
                # pivots straight to the offending trace in _selftrace.
                # NOT rate-limited like the log line — the recorder's
                # deque is the bound
                if RECORDER.enabled:
                    RECORDER.record(
                        TRIGGER_SLOW_QUERY, trace_id=qs.trace_id,
                        detail={"tenant": qs.tenant, "scope": qs.scope,
                                "wall_s": round(qs.wall_s, 3),
                                "threshold_s": self.slow_s})
        return d

    def snapshot(self, recent: int = 32) -> dict:
        with self._lock:
            return {
                "enabled": self.enabled,
                "slow_query_log_s": self.slow_s,
                "published": self._published,
                "tenants": {k: dict(v, device_seconds=round(
                    v["device_seconds"], 6))
                    for k, v in sorted(self._tenants.items())},
                "top_by_device_seconds": [d for _, d in self._top_device],
                "top_by_bytes": [d for _, d in self._top_bytes],
                "recent": list(self._ring)[-recent:] if recent > 0 else [],
            }

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
            self._tenants.clear()
            self._top_device.clear()
            self._top_bytes.clear()
            self._limiter = _SlowLogLimiter()
            self._published = 0


REGISTRY = QueryStatsRegistry()


def configure(enabled: bool | None = None, slow_s: float | None = None,
              ring_size: int | None = None) -> QueryStatsRegistry:
    """Apply TempoDBConfig.search_query_stats_* / search_slow_query_log_s
    to the process registry (most recent TempoDB wins, the profiler /
    metrics idiom)."""
    if enabled is not None:
        REGISTRY.enabled = bool(enabled)
    if slow_s is not None:
        REGISTRY.slow_s = float(slow_s)
    if ring_size is not None:
        with REGISTRY._lock:
            REGISTRY._ring = deque(REGISTRY._ring, maxlen=int(ring_size))
    return REGISTRY


def query_summary(req) -> dict:
    """Low-cardinality request summary for the stats record (never the
    raw tag VALUES at full fidelity — the slow log is greppable, not a
    data exfiltration channel; tags are the operator's own predicates
    though, so keep them)."""
    try:
        tags = dict(req.tags)
        out = {
            "tags": tags,
            "limit": req.limit or 20,
            "window_s": ((req.end - req.start)
                         if req.end and req.start else 0),
        }
        from .structural import STRUCTURAL_QUERY_TAG

        raw = tags.pop(STRUCTURAL_QUERY_TAG, None)
        if raw is not None:
            # the reserved transport tag is percent-quoted JSON — the
            # slow log / debug ring should show the operator's query,
            # not its wire escaping
            import urllib.parse

            out["structural_q"] = urllib.parse.unquote(raw)
        return out
    except Exception:  # noqa: BLE001 — diagnostics never fail a query
        return {}


def begin(tenant: str, req=None, scope: str = "exec") -> QueryStats | None:
    """A new QueryStats when the layer is enabled, else None — the ONE
    branch the disabled path pays. (Explain routing stays with the
    REQUEST — the finalize sites read req.explain — so the record
    carries no copy of it.)"""
    if not REGISTRY.enabled:
        return None
    return QueryStats(tenant, scope=scope,
                      query=query_summary(req) if req is not None else {})


@contextlib.contextmanager
def activate(qs: QueryStats | None):
    """Make `qs` the thread's active stats for the duration (contextvar;
    None = noop). Deep layers record via current() without any
    parameter threading."""
    if qs is None:
        yield None
        return
    token = _ACTIVE.set(qs)
    try:
        yield qs
    finally:
        _ACTIVE.reset(token)


def current() -> QueryStats | None:
    return _ACTIVE.get()


@contextlib.contextmanager
def fronted():
    """Mark this thread as executing sub-requests for an in-process
    frontend (see _FRONTED) — QueryFrontend wraps its worker-pool job
    bodies with this."""
    token = _FRONTED.set(True)
    try:
        yield
    finally:
        _FRONTED.reset(token)


# per-thread count of attributions made by nested attributed_dispatch
# contexts: an outer context must not wall-fallback when an inner one
# already billed the work (the profiler's record collector hands each
# record to the INNERMOST collector only, so the outer sees none)
_attr_local = threading.local()


@contextlib.contextmanager
def attributed_dispatch(qs: QueryStats | None = None,
                        fallback_wall: bool = True):
    """Attribute every profiler dispatch record finished inside the
    body to `qs` (default: the active stats), 100% — the non-fused
    dispatch sites (batched, mesh, dict-probe during query compile). With profiling disabled (no records), the measured wall
    time of the body is attributed as stage "execute" so device-seconds
    accounting degrades gracefully instead of to zero — unless
    `fallback_wall` is False (bodies that are mostly host work and only
    SOMETIMES dispatch, like query compilation). Nests safely: a body
    that itself attributes bills once, never twice."""
    from tempo_tpu.observability import profile

    qs = qs if qs is not None else current()
    if qs is None:
        yield
        return
    before = getattr(_attr_local, "consumed", 0)
    t0 = time.perf_counter()
    with profile.collect_records() as recs:
        yield
    wall = time.perf_counter() - t0
    if recs:
        for rd in recs:
            stages = {k: v / 1e3
                      for k, v in (rd.get("stages_ms") or {}).items()}
            qs.add_device_stages(stages,
                                 h2d_bytes=rd.get("h2d_bytes", 0))
        _attr_local.consumed = before + 1
    elif fallback_wall and getattr(_attr_local, "consumed", 0) == before:
        qs.add_device_stages({"execute": wall})
        _attr_local.consumed = before + 1
