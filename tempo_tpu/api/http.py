"""HTTP API: the external read surface + operational endpoints.

Role-equivalent to the reference's HTTP routes (pkg/api/http.go:49-55,
cmd/tempo/app/app.go:380-511): /api/traces/{id}, /api/search,
/api/search/tags, /api/search/tag/{name}/values, /api/echo, plus /ready,
/metrics, /status, /flush and /shutdown. Multi-tenant via X-Scope-OrgID
(fake-auth default tenant when absent, reference fake_auth.go). JSON
bodies via protobuf json_format.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import urlparse, parse_qs

from google.protobuf import json_format

from tempo_tpu.modules.distributor import RateLimited
from tempo_tpu.modules.queue import TooManyRequests
from tempo_tpu.utils.ids import hex_to_trace_id
from .params import (
    DEFAULT_TENANT,
    HEADER_TENANT,
    PATH_ECHO,
    PATH_SEARCH,
    PATH_SEARCH_STREAM,
    PATH_SEARCH_TAGS,
    PATH_SEARCH_TAG_VALUES,
    PATH_TAIL,
    PATH_TRACES,
    InvalidArgument,
    parse_search_request,
    parse_trace_by_id_params,
)


def _hex_trace_id(s: str) -> bytes:
    """URL trace ids are client input: bad hex is a 400, not a 500."""
    try:
        return hex_to_trace_id(s)
    except ValueError as e:
        raise InvalidArgument(str(e)) from None


class TextBody(str):
    """A text response body carrying its own Content-Type. A str
    subclass, so handle() callers that compare/parse the body are
    unaffected — only the wire serializer (_reply) looks at the
    attribute. /metrics uses it: Prometheus scrapers key the parser off
    `text/plain; version=0.0.4` vs the OpenMetrics media type."""

    __slots__ = ("content_type",)

    def __new__(cls, s: str, content_type: str):
        self = super().__new__(cls, s)
        self.content_type = content_type
        return self


class SSEBody:
    """A streaming response body: an iterator of pre-rendered
    Server-Sent-Event frames. Unlike TextBody this is NOT a str — the
    whole point is that the wire serializer must not buffer it. _reply
    writes each frame as it arrives (Content-Type: text/event-stream, no
    Content-Length, flush per event); handle() callers in tests iterate
    `.events` directly. close() closes the underlying generator so its
    `finally` blocks run (tail routes unsubscribe there) even when the
    client hangs up mid-stream."""

    content_type = "text/event-stream"

    def __init__(self, events):
        self.events = events

    def close(self) -> None:
        close = getattr(self.events, "close", None)
        if close is not None:
            close()


def _sse_event(name: str, doc: dict) -> str:
    """One SSE frame. data: is a single line — json.dumps never emits
    raw newlines — so the event ends at the blank line per the spec."""
    return f"event: {name}\ndata: {json.dumps(doc)}\n\n"


def _int_param(query: dict, key: str, default: int) -> int:
    """Non-negative int query param with a default (the /debug routes'
    `recent` knob); garbage falls back rather than 500s a debug page."""
    try:
        return max(0, int(query.get(key, default)))
    except (TypeError, ValueError):
        return default


def _route_template(path: str) -> str:
    """Collapse variable path segments so span names stay low-cardinality
    (OTel convention: name by route, real path in http.target)."""
    parts = path.split("/")
    if len(parts) >= 4 and parts[1] == "api" and parts[2] == "traces":
        parts[3] = "{id}"
    elif (len(parts) >= 5 and parts[1] == "api" and parts[2] == "search"
          and parts[3] == "tag"):
        parts[4] = "{tag}"
    elif len(parts) >= 5 and parts[1] == "jaeger" and parts[3] == "traces":
        parts[4] = "{id}"
    elif len(parts) >= 5 and parts[1] == "jaeger" and parts[3] == "services":
        parts[4] = "{service}"
    return "/".join(parts)


class HTTPApi:
    """Routes HTTP requests onto an App (modules/app.py)."""

    def __init__(self, app, multitenancy: bool = True,
                 debug_endpoints: bool = True):
        self.app = app
        self.multitenancy = multitenancy
        # /debug/* dumps full stacks (file paths, internals) to anyone
        # who can reach the port; deployments keep it off the public
        # port unless server.debug_endpoints says otherwise (ADVICE r4).
        # Library/test default stays on — there is no network exposure
        # until someone serves this object.
        self.debug_endpoints = debug_endpoints

    def tenant(self, headers) -> str:
        from .params import validate_tenant

        if not self.multitenancy:
            return DEFAULT_TENANT
        # ValueError → the handle() 400 path: a tenant id is the one
        # header that reaches filesystem joins
        return validate_tenant(headers.get(HEADER_TENANT) or DEFAULT_TENANT)

    def handle(self, method: str, path: str, query: dict, headers,
               body: bytes = b"") -> tuple[int, dict | str]:
        from tempo_tpu.observability import tracing

        # under serve_http the `http.request` root is open and already
        # took the caller's traceparent: this span is its child
        parent = (None if tracing.current_span().recording
                  else tracing.extract_traceparent(headers))
        with tracing.start_span(f"HTTP {method} {_route_template(path)}",
                                kind=tracing.KIND_SERVER,
                                parent=parent) as span:
            span.set_attribute("http.target", path)
            try:
                if method == "POST" and path in ("/v1/traces", "/api/v2/spans",
                                                 "/api/traces"):
                    code, resp = self._ingest(path, body, headers)
                else:
                    code, resp = self._route(method, path, query, headers)
            except InvalidArgument as e:
                # ONLY the dedicated client-data type maps to 400; a
                # plain ValueError (corrupt WAL entry, object framing)
                # is server-side and falls through to the 500 handler —
                # same split as the gRPC layer (ADVICE r4)
                code, resp = 400, {"error": str(e)}
            except TooManyRequests as e:
                # tenant's fair-queue is full (reference frontend v1
                # max-outstanding → HTTP 429)
                code, resp = 429, {"error": f"too many outstanding requests: {e}"}
            except RateLimited as e:
                # ingest pushback (rate / live-traces / trace-bytes
                # limits) is retryable tenant backpressure — the
                # reference answers ResourceExhausted/FailedPrecondition,
                # i.e. 429 on the HTTP write path, never 500
                code, resp = 429, {"error": str(e)}
            except Exception as e:  # noqa: BLE001 — surface as 500
                span.record_exception(e)
                code, resp = 500, {"error": f"{type(e).__name__}: {e}"}
            span.set_attribute("http.status_code", code)
            if code >= 500:
                span.set_status(tracing.STATUS_ERROR)
            return code, resp

    def _ingest(self, path: str, body: bytes, headers):
        """HTTP ingest receivers: OTLP/HTTP protobuf and Zipkin v2 JSON
        (api/receivers.py). Malformed payloads are CLIENT errors — a 500
        would make exporters retry their own bad bodies forever."""
        import json as _json

        from google.protobuf.message import DecodeError

        from .jaeger import jaeger_thrift_http_to_batches
        from .receivers import otlp_http_to_batches, zipkin_json_to_batches
        from .thriftproto import ThriftError

        tenant = self.tenant(headers)
        try:
            if path == "/v1/traces":
                batches = otlp_http_to_batches(body)
            elif path == "/api/traces":
                # jaeger collector contract: thrift-binary Batch body
                batches = jaeger_thrift_http_to_batches(body)
            else:
                batches = zipkin_json_to_batches(body)
        except (DecodeError, KeyError, TypeError, AttributeError,
                ThriftError, ValueError, _json.JSONDecodeError) as e:
            # ValueError here is a DECODER error (bad hex id, non-array
            # zipkin body) — client payload, unlike the serving path
            # where bare ValueError means server-side corruption
            return 400, {"error": f"malformed payload: {type(e).__name__}: {e}"}
        if batches:
            self.app.push(tenant, batches)
        return 200, {"accepted_batches": len(batches)}

    def _route(self, method, path, query, headers):
        tenant = self.tenant(headers)
        if path == PATH_ECHO:
            return 200, "echo"
        if path == "/ready":
            return (200, "ready") if self.app.ready() else (503, "not ready")
        if path == "/metrics":
            from tempo_tpu.observability.metrics import (
                OPENMETRICS_CONTENT_TYPE, PROM_CONTENT_TYPE, REGISTRY)

            # OpenMetrics negotiation: scrapers that Accept the
            # openmetrics media type get exemplars (histogram buckets →
            # self-trace ids); everyone else gets the classic 0.0.4 text
            # format, byte-identical to before
            accept = (headers.get("Accept") or "") \
                if hasattr(headers, "get") else ""
            om = "application/openmetrics-text" in accept
            return 200, TextBody(
                REGISTRY.expose(openmetrics=om),
                OPENMETRICS_CONTENT_TYPE if om else PROM_CONTENT_TYPE)
        if path == "/status" or path.startswith("/status/"):
            return 200, self._status(path, query)
        if path == "/flush":
            completed = self.app.flush_tick(force=True)
            return 200, {"completed_blocks": len(completed)}
        if path.startswith("/debug/"):
            # ONE gate + ONE registry for every /debug route: a route
            # registered in DEBUG_ROUTES is automatically covered by the
            # server.debug_endpoints gate and by the tier-1 contract
            # test (tests/test_debug_routes.py — every route must answer
            # valid JSON when enabled and 404 when gated off)
            if not self.debug_endpoints:
                return 404, {"error": "debug endpoints disabled "
                                      "(server.debug_endpoints: true "
                                      "enables)"}
            handler = DEBUG_ROUTES.get(path)
            if handler is not None:
                return handler(self, query)
        if path == "/shutdown":
            threading.Thread(target=self.app.shutdown, daemon=True).start()
            return 200, "shutting down"

        # content negotiation (reference querier/frontend internal proto
        # marshalling, frontend.go:121-127): a client that Accepts
        # application/protobuf gets the wire message, not its JSON form
        accept = (headers.get("Accept") or "") if hasattr(headers, "get") \
            else ""
        want_proto = "application/protobuf" in accept

        if path.startswith(PATH_TRACES + "/"):
            trace_id = _hex_trace_id(path[len(PATH_TRACES) + 1:])
            mode, bs, be = parse_trace_by_id_params(query)
            with self._request_deadline(headers):
                resp = self.app.find_trace(tenant, trace_id)
            if not resp.trace.batches:
                return 404, {"error": "trace not found"}
            code = 206 if resp.metrics.failed_blocks else 200
            if want_proto:
                return code, resp.trace.SerializeToString()
            return code, json_format.MessageToDict(resp.trace)
        if path == PATH_SEARCH_STREAM:
            return self._search_stream(tenant, query, headers)
        if path == PATH_TAIL:
            return self._tail_stream(tenant, query)
        if path == PATH_SEARCH:
            req = self._parse_search(query, headers)
            # request deadline: X-Tempo-Timeout-S header, else the
            # search_request_timeout_s config default — propagates
            # http → frontend → querier → TempoDB via the worker
            # pool's contextvars copy (robustness/deadline.py), so
            # sharded sub-queries stop queueing behind a dead device
            with self._request_deadline(headers):
                resp = self.app.search(tenant, req)
            # tolerated block failures / deadline-clipped answers =
            # partial (reference frontend.go:144-146 semantics,
            # extended to search)
            code = 206 if (resp.metrics.failed_blocks
                           or resp.metrics.partial) else 200
            if want_proto:
                return code, resp.SerializeToString()
            doc = json_format.MessageToDict(resp)
            if resp.metrics.query_stats_json:
                # inline the breakdown as a real JSON object instead of
                # an escaped string riding the metrics message
                try:
                    doc["queryStats"] = json.loads(
                        resp.metrics.query_stats_json)
                    doc.get("metrics", {}).pop("queryStatsJson", None)
                except ValueError:
                    pass
            if resp.metrics.agg_json:
                # the ?agg= aggregate, inlined as a real JSON object
                # like queryStats above
                try:
                    doc["aggregates"] = json.loads(resp.metrics.agg_json)
                    doc.get("metrics", {}).pop("aggJson", None)
                except ValueError:
                    pass
            return code, doc
        if path == PATH_SEARCH_TAGS:
            resp = self.app.queriers[0].search_tags(tenant)
            return 200, json_format.MessageToDict(resp)
        if path.startswith(PATH_SEARCH_TAG_VALUES + "/"):
            rest = path[len(PATH_SEARCH_TAG_VALUES) + 1:]
            if rest.endswith("/values"):
                tag = rest[: -len("/values")]
                if not tag:
                    return 400, {"error": "empty tag name"}
                resp = self.app.queriers[0].search_tag_values(tenant, tag)
                return 200, json_format.MessageToDict(resp)
        if path.startswith("/jaeger/api/"):
            return self._jaeger_query(tenant, path[len("/jaeger/api"):], query)
        return 404, {"error": f"no route {path}"}

    def _jaeger_query(self, tenant, sub, query):
        """Jaeger query-service JSON API (cmd/tempo-query role)."""
        from .jaeger_query import JaegerQueryBridge

        bridge = JaegerQueryBridge(self.app)
        if sub == "/services":
            return 200, bridge.services(tenant)
        if sub.startswith("/services/") and sub.endswith("/operations"):
            svc = sub[len("/services/"): -len("/operations")]
            return 200, bridge.operations(tenant, svc)
        if sub == "/operations":
            return 200, bridge.operations(tenant, query.get("service", ""))
        if sub == "/dependencies":
            return 200, bridge.dependencies()
        if sub == "/traces":
            return 200, bridge.search(tenant, query)
        if sub.startswith("/traces/"):
            data = bridge.trace_by_id(tenant,
                                      _hex_trace_id(sub[len("/traces/"):]))
            if data is None:
                return 404, {"errors": [{"msg": "trace not found"}]}
            return 200, data
        return 404, {"error": f"no jaeger route {sub}"}

    def _request_deadline(self, headers):
        """The query routes' request deadline: the X-Tempo-Timeout-S
        header wins (bad values ignored — a garbage header must not 400
        a query that never asked for a deadline), else the
        search_request_timeout_s config default; <= 0 / absent = no
        deadline, the historical unbounded behavior."""
        from tempo_tpu.robustness import deadline as rdeadline

        timeout = None
        raw = (headers.get("X-Tempo-Timeout-S")
               if hasattr(headers, "get") else None)
        if raw:
            try:
                timeout = float(raw)
            except (TypeError, ValueError):
                timeout = None
        if timeout is None:
            db_cfg = getattr(getattr(self.app, "cfg", None), "db", None)
            timeout = getattr(db_cfg, "search_request_timeout_s", 0.0)
        return rdeadline.start(timeout)

    # ---- streaming search + live tail (docs/search-live-tail.md) ----

    def _parse_search(self, query, headers):
        """Shared request prep for /api/search and /api/search/stream:
        parse, structural-gate check, explain opt-in."""
        req = parse_search_request(query)
        from tempo_tpu.search.structural import (STRUCTURAL,
                                                 STRUCTURAL_QUERY_TAG)

        if STRUCTURAL_QUERY_TAG in req.tags and not STRUCTURAL.enabled:
            # structural queries are gated per deployment
            # (docs/search-structural-queries.md): a clear client
            # error, not a silent legacy-scan answer
            raise InvalidArgument("structural queries disabled "
                                  "(storage.search_structural_"
                                  "enabled: true enables)")
        from tempo_tpu.search.analytics import ANALYTICS, AGG_QUERY_TAG

        if AGG_QUERY_TAG in req.tags and not ANALYTICS.enabled:
            # ?agg= is gated per deployment (docs/search-analytics.md):
            # a clear client error, not a silent plain-search answer
            # missing the aggregate the caller asked for
            raise InvalidArgument("search aggregation disabled "
                                  "(storage.search_analytics_"
                                  "enabled: true enables)")
        # explain opt-in: ?explain=1 (parse_search_request) or the
        # X-Tempo-Explain header — the response then carries the
        # full per-query execution breakdown. Same value set as the
        # query param: "X-Tempo-Explain: 0" must NOT opt in
        if hasattr(headers, "get") and \
                (headers.get("X-Tempo-Explain") or "").strip().lower() \
                in ("1", "true", "yes"):
            req.explain = True
        return req

    def _search_stream(self, tenant, query, headers):
        """Progressive search: the same fan-out as /api/search, but each
        sub-response merge that grew the result set streams a `result`
        snapshot event immediately — hot-tier/ingester legs answer in
        milliseconds while backend block groups are still scanning. The
        final `done` event carries the complete merged response
        (byte-equivalent to what /api/search would have returned)."""
        import contextvars
        import queue as _queue

        from tempo_tpu.observability import metrics as obs
        from tempo_tpu.observability import tracing

        req = self._parse_search(query, headers)
        q: _queue.Queue = _queue.Queue()

        # copied context: the worker's frontend/search spans parent
        # under the HTTP request span instead of starting orphan traces
        ctx = contextvars.copy_context()

        def run():
            # worker thread: contextvars are thread-local, so the
            # request deadline must be entered HERE for the frontend's
            # pool-copy propagation to pick it up
            try:
                with self._request_deadline(headers):
                    resp = self.app.search(
                        tenant, req,
                        on_progress=lambda r: q.put(("result", r)))
                q.put(("done", resp))
            except Exception as e:  # noqa: BLE001 — ship to the stream
                q.put(("error", e))

        threading.Thread(target=ctx.run, args=(run,), daemon=True,
                         name="search-stream").start()

        # the generator drains AFTER handle()'s request span closed (the
        # server writes frames as they arrive), so the streaming leg
        # gets its own span parented under the request — ended manually,
        # never made current: the consuming thread/context is not ours
        parent = tracing.current_span().context

        def events():
            obs.sse_active_streams.add(1, endpoint="search_stream",
                                       tenant=tenant)
            span = tracing.start_span("sse.search_stream", parent=parent,
                                      tenant=tenant)
            n = 0
            try:
                while True:
                    kind, payload = q.get()
                    if kind == "error":
                        obs.sse_events_streamed.inc(
                            endpoint="search_stream", tenant=tenant,
                            event="error")
                        if span.recording:
                            span.set_status(
                                tracing.STATUS_ERROR, str(payload))
                        yield _sse_event("error", {
                            "error":
                                f"{type(payload).__name__}: {payload}"})
                        return
                    doc = json_format.MessageToDict(payload)
                    obs.sse_events_streamed.inc(
                        endpoint="search_stream", tenant=tenant,
                        event=kind)
                    n += 1
                    yield _sse_event(kind, doc)
                    if kind == "done":
                        return
            finally:
                if span.recording:
                    span.set_attribute("events", n)
                span.end()
                obs.sse_active_streams.add(-1, endpoint="search_stream",
                                           tenant=tenant)

        return 200, SSEBody(events())

    def _tail_stream(self, tenant, query):
        """Live tail: a standing query at the ingest path. Every pushed
        trace that matches streams a `trace` event within the push's
        micro-batch — no poll loop against /api/search needed."""
        import time as _time

        from tempo_tpu.observability import metrics as obs
        from tempo_tpu.observability import tracing

        req = self._parse_search(query, headers={})
        sub = self.app.tail_subscribe(tenant, req)
        if sub is None:
            from tempo_tpu.search.live_tier import LIVE_TIER

            if not LIVE_TIER.enabled:
                return 400, {"error": "live tail disabled "
                                      "(storage.search_live_tier_"
                                      "enabled: true enables)"}
            return 429, {"error": "tail subscription cap reached for "
                                  "tenant"}
        # bounded by default: an abandoned curl must not hold a
        # subscription slot forever (the cap is per tenant)
        seconds = min(_int_param(query, "seconds", 30), 3600)
        deadline = _time.monotonic() + seconds
        # streaming-leg span: same stance as _search_stream — ended
        # manually, never made current (the generator drains on the
        # server writer thread after the request span closed)
        parent = tracing.current_span().context

        def events():
            obs.sse_active_streams.add(1, endpoint="tail", tenant=tenant)
            span = tracing.start_span("sse.tail", parent=parent,
                                      tenant=tenant, seconds=seconds)
            booked = obs.sse_events_streamed
            n = 0
            try:
                booked.inc(endpoint="tail", tenant=tenant,
                           event="subscribed")
                yield _sse_event("subscribed", {"seconds": seconds})
                while True:
                    remaining = deadline - _time.monotonic()
                    if remaining <= 0:
                        booked.inc(endpoint="tail", tenant=tenant,
                                   event="done")
                        yield _sse_event("done", {"reason": "duration"})
                        return
                    metas = sub.poll(min(remaining, 1.0))
                    if not metas:
                        # SSE comment = keepalive; proxies and clients
                        # see bytes flowing on an idle tail
                        booked.inc(endpoint="tail", tenant=tenant,
                                   event="keepalive")
                        yield ": keepalive\n\n"
                        continue
                    for m in metas:
                        booked.inc(endpoint="tail", tenant=tenant,
                                   event="trace")
                        n += 1
                        yield _sse_event(
                            "trace", json_format.MessageToDict(m))
            finally:
                # runs on generator close() too — client hangup mid-
                # stream must release the tenant's subscription slot
                self.app.tail_unsubscribe(sub)
                if span.recording:
                    span.set_attribute("events", n)
                    span.set_attribute("dropped", sub.dropped)
                span.end()
                obs.sse_active_streams.add(-1, endpoint="tail",
                                           tenant=tenant)

        return 200, SSEBody(events())

    # ---- /debug/* route handlers (registered in DEBUG_ROUTES) ----

    def _debug_threads_route(self, query):
        # faulthandler-style all-thread stack dump (reference pprof
        # goroutine profile role, cmd/tempo/main.go:54-115): the
        # first tool for "this process is stuck where?"
        return 200, self._debug_threads()

    def _debug_scan_route(self, query):
        # per-stage breakdown of the last scan + cache occupancy
        db = getattr(self.app, "reader_db", None)
        if db is None:
            return 404, {"error": "no storage reader in this target"}
        return 200, db.batcher.debug_stats()

    def _debug_profile_route(self, query):
        # dispatch profiler: recent per-dispatch stage breakdowns +
        # process-lifetime aggregates (observability/profile.py)
        from tempo_tpu.observability.profile import PROFILER

        return 200, PROFILER.snapshot(
            recent=_int_param(query, "recent", 32))

    def _debug_planner_route(self, query):
        # offload planner: decision ring, cost-model rates,
        # predicted-vs-actual calibration (search/planner.py)
        from tempo_tpu.search.planner import PLANNER

        return 200, PLANNER.snapshot(
            recent=_int_param(query, "recent", 32))

    def _debug_querystats_route(self, query):
        # per-query inspector: recent queries, per-tenant
        # device-seconds/bytes aggregates, top-K by cost
        # (search/query_stats.py)
        from tempo_tpu.search.query_stats import REGISTRY

        return 200, REGISTRY.snapshot(
            recent=_int_param(query, "recent", 32))

    def _debug_faults_route(self, query):
        # robustness state: the fault-injection registry (catalog +
        # live arming) and the device circuit breaker's state machine
        # (tempo_tpu/robustness/)
        from tempo_tpu.robustness import BREAKER, FAULTS, GUARD

        return 200, {
            "faults": FAULTS.snapshot(),
            "breaker": BREAKER.snapshot(),
            "dispatch_guard": {
                "active": GUARD.active,
                "timeout_s": GUARD.timeout_s,
                "lock_timeout_s": GUARD.lock_timeout_s,
            },
        }

    def _debug_ownership_route(self, query):
        # owner-routed HBM: the placement map (group -> owner),
        # membership generation, and this process's per-group residency
        # (search/ownership.py + the batcher's staged-cache view)
        from tempo_tpu.search.ownership import OWNERSHIP

        snap = OWNERSHIP.snapshot()
        db = getattr(self.app, "reader_db", None)
        if db is not None:
            snap["residency"] = db.batcher.cache.ownership_residency()
        return 200, snap

    def _debug_flightrecorder_route(self, query):
        # anomaly flight recorder: bounded diagnostic bundles captured
        # at breaker trips / watchdog fires / slow queries, each with
        # the offending self-trace id — resolvable in _selftrace while
        # the dogfood pipeline (selftrace_ingest_enabled) is on
        # (observability/flightrecorder.py)
        from tempo_tpu.observability.flightrecorder import RECORDER

        return 200, RECORDER.snapshot(
            recent=_int_param(query, "recent", 32))

    def _debug_ingest_route(self, query):
        # write-path telemetry: per-tenant live/unflushed/backlog state,
        # last flush/poll ages, WAL replay, slow-flush ring, canary
        # (observability/ingest_telemetry.py)
        from tempo_tpu.observability.ingest_telemetry import TELEMETRY

        return 200, TELEMETRY.debug_snapshot(app=self.app)

    def _debug_threads(self) -> str:
        """All-thread stack dump as plain text. Pure-Python equivalent of
        faulthandler.dump_traceback (which needs a real fd, not a
        response body): name each thread and format its current frame
        stack, so a hung flush/scan/stream shows exactly where it sits."""
        import sys
        import traceback

        names = {t.ident: t.name for t in threading.enumerate()}
        out = []
        for ident, frame in sorted(sys._current_frames().items()):
            out.append(f"--- thread {names.get(ident, '?')} ({ident}) ---")
            out.extend(line.rstrip()
                       for line in traceback.format_stack(frame))
        return "\n".join(out) + "\n"

    def _status(self, path, query: dict | None = None) -> dict:
        app = self.app
        if path == "/status/config":
            # reference /status/config?mode=diff|defaults (app.go:332-378)
            return self._status_config((query or {}).get("mode", ""))
        from tempo_tpu.observability.ingest_telemetry import TELEMETRY
        from tempo_tpu.observability.profile import build_info, device_status

        out = {
            "ready": app.ready(),
            # build/runtime identity (the tempo_build_info gauge's
            # labels, re-evaluated live — backend/native may have
            # initialized since the gauge was set at App init)
            "build": build_info(),
            "ring": {
                "instances": app.ring.instance_ids(),
                "healthy": app.ring.healthy_count(),
                "replication_factor": app.ring.rf,
            },
            # accelerator health at a glance: backend, device kind and
            # count, per-device memory, age of the last successful
            # dispatch — the hung-vs-idle signal (never initializes a
            # backend on processes that haven't touched the device)
            "device": device_status(),
            # search freshness at a glance (the write-path twin of the
            # device block): per-tenant staleness, oldest unflushed
            # trace age, last poll age, canary verdict
            "ingest": TELEMETRY.status(),
        }
        db = getattr(app, "reader_db", None)
        if db is not None:  # targets without a storage reader (distributor)
            out["tenants"] = db.blocklist.tenants()
            out["blocks"] = {t: len(db.blocklist.metas(t))
                             for t in db.blocklist.tenants()}
        dispatcher = getattr(app, "dispatcher", None)
        if dispatcher is not None:  # query-frontend pull dispatch
            out["pull_dispatch"] = {
                "workers": dispatcher.workers(),
                "queued": dispatcher.queued(),
                "delivered": dispatcher.delivered,
                "requeued": dispatcher.requeued,
            }
        return out

    _SECRET_KEY_RE = None  # compiled lazily below

    @classmethod
    def _redact(cls, node):
        """Secrets must not leak on the tenant-facing port: any key that
        looks credential-bearing gets its whole value replaced."""
        import re

        if cls._SECRET_KEY_RE is None:
            cls._SECRET_KEY_RE = re.compile(
                r"secret|password|token|credential|authorization|headers"
                r"|access_key|account_key|sasl", re.I)
        if isinstance(node, dict):
            return {
                k: ("<redacted>" if cls._SECRET_KEY_RE.search(str(k))
                    else cls._redact(v))
                for k, v in node.items()
            }
        if isinstance(node, list):
            return [cls._redact(v) for v in node]
        return node

    def _status_config(self, mode: str) -> dict:
        """Running config as a dict (secrets redacted); mode=defaults
        shows the built-in defaults, mode=diff only the changed keys."""
        import dataclasses

        def to_dict(cfg):
            return self._redact(dataclasses.asdict(cfg))

        from tempo_tpu.modules import AppConfig

        current = to_dict(self.app.cfg)
        if mode == "defaults":
            return to_dict(AppConfig())
        if mode == "diff":
            def diff(cur, dfl):
                out = {}
                for k, cv in cur.items():
                    dv = dfl.get(k) if isinstance(dfl, dict) else None
                    if isinstance(cv, dict) and isinstance(dv, dict):
                        sub = diff(cv, dv)
                        if sub:
                            out[k] = sub
                    elif cv != dv:
                        out[k] = cv
                return out

            return diff(current, to_dict(AppConfig()))
        return current


# every /debug route: path -> handler(api, query) -> (code, body).
# Adding a route HERE is all it takes — the server.debug_endpoints gate
# in _route and the tier-1 JSON/gating contract test iterate this map.
DEBUG_ROUTES = {
    "/debug/threads": HTTPApi._debug_threads_route,
    "/debug/scan": HTTPApi._debug_scan_route,
    "/debug/profile": HTTPApi._debug_profile_route,
    "/debug/planner": HTTPApi._debug_planner_route,
    "/debug/querystats": HTTPApi._debug_querystats_route,
    "/debug/ingest": HTTPApi._debug_ingest_route,
    "/debug/faults": HTTPApi._debug_faults_route,
    "/debug/ownership": HTTPApi._debug_ownership_route,
    "/debug/flightrecorder": HTTPApi._debug_flightrecorder_route,
}


def _accepts_gzip(header: str | None) -> bool:
    """RFC 9110 Accept-Encoding: gzip only when listed with q > 0 —
    `gzip;q=0` is an explicit refusal, not a match."""
    for token in (header or "").lower().split(","):
        parts = [p.strip() for p in token.split(";")]
        if parts[0] != "gzip":
            continue
        for p in parts[1:]:
            if p.startswith("q="):
                try:
                    return float(p[2:]) > 0
                except ValueError:
                    return False
        return True
    return False


def serve_http(api: HTTPApi, host: str = "0.0.0.0", port: int = 3200):
    """Blocking stdlib server; returns the server object when used via
    threading (tests call .shutdown())."""

    from tempo_tpu.observability import tracing

    class Accepted(tuple):
        """A client address that carries the accept stamp from the
        accept thread to the handler thread."""

    class Server(ThreadingHTTPServer):
        # connections the kernel holds while the accept thread waits for
        # its turn at the interpreter lock. The stdlib's 5 overflows at
        # ~150 requests/s from sixteen callers that each open a
        # connection a request: the kernel drops the SYN and the client
        # sends it again after 1 s, then 3, 7, 15 (p99 1.07 s and one
        # request of 7-16 s a window on a v5e host, PERF.md section 6,
        # PR 40)
        request_queue_size = 128

        def get_request(self):
            request, addr = super().get_request()
            if tracing.get_tracer() is not None:
                addr = Accepted(addr)
                addr.accept_ns = tracing.now_ns()
            return request, addr

    class Handler(BaseHTTPRequestHandler):
        def _request_span(self):
            """`http.request`, the root of a served request's trace:
            from the accept (before the handler thread started and the
            headers were parsed) to the last byte of the reply. One
            request per connection (HTTP/1.0), so the accept stamp
            belongs to this request."""
            if tracing.get_tracer() is None:
                return tracing.NOOP_SPAN
            entered = tracing.now_ns()
            accept_ns = getattr(self.client_address, "accept_ns", entered)
            return tracing.start_span(
                "http.request", kind=tracing.KIND_SERVER,
                parent=tracing.extract_traceparent(self.headers),
                start_ns=accept_ns,
                accept_wait_ms=(entered - accept_ns) / 1e6)

        def do_GET(self):  # noqa: N802 — stdlib API
            with self._request_span():
                u = urlparse(self.path)
                query = {k: v[0] for k, v in parse_qs(u.query).items()}
                code, body = api.handle("GET", u.path, query, self.headers)
                self._reply(code, body)

        def do_POST(self):  # noqa: N802
            with self._request_span():
                self._post()

        def _post(self):
            u = urlparse(self.path)
            query = {k: v[0] for k, v in parse_qs(u.query).items()}
            MAX_BODY = 64 << 20  # cap hostile/streaming bodies
            if self.headers.get("Transfer-Encoding", "").lower() == "chunked":
                chunks, total = [], 0
                try:
                    while True:
                        size_line = self.rfile.readline().split(b";")[0].strip()
                        size = int(size_line, 16)
                        if size < 0:
                            raise ValueError("negative chunk size")
                        if size == 0:
                            self.rfile.readline()  # trailing CRLF
                            break
                        total += size
                        if total > MAX_BODY:
                            raise ValueError("body too large")
                        chunks.append(self.rfile.read(size))
                        self.rfile.readline()  # chunk CRLF
                except ValueError as e:
                    return self._reply(400, {"error": f"bad chunked body: {e}"})
                body = b"".join(chunks)
            else:
                length = int(self.headers.get("Content-Length", 0))
                if length > MAX_BODY:
                    # reject, never truncate: a parseable prefix would be
                    # silently accepted while the tail spans are dropped
                    return self._reply(413, {"error": "body too large"})
                body = self.rfile.read(length) if length else b""
            code, out = api.handle("POST", u.path, query, self.headers, body)
            self._reply(code, out)

        def _reply(self, code, body):
            """Render, compress and write; `http.reply` spans it and
            the open `http.request` takes the status and the size."""
            with tracing.start_span("http.reply") as span:
                sent = self._write(code, body)
                if span.recording:
                    span.set_attribute("bytes", sent)
            root = tracing.current_span()
            if root.recording:
                root.set_attributes(**{"http.status_code": code,
                                       "bytes": sent})

        def _write(self, code, body) -> int:
            if isinstance(body, SSEBody):
                # streaming: no Content-Length, no gzip, flush per
                # event — buffering would defeat the route's purpose
                self.send_response(code)
                self.send_header("Content-Type", body.content_type)
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                self.end_headers()
                sent = 0
                try:
                    for frame in body.events:
                        sent += self.wfile.write(frame.encode())
                        self.wfile.flush()
                except (BrokenPipeError, ConnectionResetError):
                    pass  # client hung up; close() below cleans up
                finally:
                    body.close()
                return sent
            if isinstance(body, (bytes, bytearray)):
                # negotiated protobuf (Accept: application/protobuf on
                # the query routes) — reference frontend.go:121-127
                data = bytes(body)
                ctype = "application/protobuf"
            elif isinstance(body, (dict, list)):
                data = json.dumps(body).encode()
                ctype = "application/json"
            else:
                data = str(body).encode()
                # TextBody carries its negotiated type (/metrics)
                ctype = getattr(body, "content_type", "text/plain")
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            # the body varies on negotiation headers — shared caches
            # must key on them or serve the wrong representation
            self.send_header("Vary", "Accept, Accept-Encoding")
            # response compression (reference gzips frontend responses);
            # tiny payloads skip it — the header+CPU outweighs the bytes
            if _accepts_gzip(self.headers.get("Accept-Encoding")) \
                    and len(data) >= 256:
                import gzip as _gzip

                data = _gzip.compress(data, compresslevel=5)
                self.send_header("Content-Encoding", "gzip")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
            return len(data)

        def log_message(self, *a):  # quiet
            pass

    server = Server((host, port), Handler)
    return server
