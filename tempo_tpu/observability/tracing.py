"""Self-tracing: the framework traces itself, like the reference does.

Role-equivalent to the reference's OpenTracing/OTel tracer init
(cmd/tempo/main.go:76-87, installOpenTelemetryTracer) and spanlogger
(pkg/util/spanlogger): every layer annotates its work with spans
(store.Find tempodb/tempodb.go:291, BackendBlock.find backend_block.go:40,
searchsharding.go:189), and the resulting trace is exported — here either
via OTLP/HTTP to any collector, or *into the framework itself* (the
classic "tempo traces tempo" deployment) through an in-process push.

Design notes (deliberately not a port of opentelemetry-sdk):
- contextvars carry the active span, so spans parent correctly across
  threads started with a copied context and across the in-process module
  graph without any plumbing.
- A zero-overhead noop path: when no tracer is installed, ``start_span``
  returns a shared immutable noop span; hot loops pay one dict lookup.
- Export suppression: while an exporter is pushing spans into the
  framework itself, tracing is suppressed on that thread — otherwise the
  self-ingest path would trace itself recursively forever.
"""

from __future__ import annotations

import contextvars
import logging
import os
import queue
import random
import struct
import threading
import time
import urllib.request

_current_span: contextvars.ContextVar = contextvars.ContextVar(
    "tempo_tpu_current_span", default=None)
_suppressed: contextvars.ContextVar = contextvars.ContextVar(
    "tempo_tpu_trace_suppressed", default=False)

# span kinds (OTLP numbering, trace.proto Span.SpanKind)
KIND_INTERNAL = 1
KIND_SERVER = 2
KIND_CLIENT = 3
KIND_PRODUCER = 4
KIND_CONSUMER = 5

STATUS_UNSET = 0
STATUS_OK = 1
STATUS_ERROR = 2

# the span clock: unix nanoseconds from ONE wall-clock anchor plus the
# monotonic counter, so stamps taken on different threads order and
# subtract exactly inside a run (time.time_ns() can step) and still
# compare with a wall_ns sampled by whoever correlates an external
# trace. Every stamp a span, a profiler stage or a batcher stage sum
# takes is a read of this one clock. The price: between two anchors
# the clock does not follow the wall clock's corrections, so exported
# times drift against other hosts' by that much (docs/observability.md).
_CLOCK_OFFSET_NS = time.time_ns() - time.perf_counter_ns()


def now_ns() -> int:
    return _CLOCK_OFFSET_NS + time.perf_counter_ns()


def cpu_ns() -> int:
    """CPU the calling thread has burned so far (user and system,
    `CLOCK_THREAD_CPUTIME_ID`): the one function every CPU stamp of a
    span goes through. Two reads at the two edges a span already stamps
    give the time its thread was on a core between them; the wall time
    less that is the time it was off it (asleep, or runnable behind the
    interpreter lock: the clock cannot tell which). Read only where a
    span will be written: with no tracer installed no site calls it.
    Its step is the kernel's: nanoseconds where the scheduler's clock is
    fine, one tick (10 ms) where CPU is accounted by the tick, as on the
    v5e hosts this was measured on; there one span's difference is 0 or
    a whole tick and only sums over many spans mean anything."""
    return time.thread_time_ns()


def _reanchor_clock() -> None:
    """Take the anchor anew once the wall clock has moved a millisecond
    off it: called when a tracer is installed, the one moment no span
    of it is open. Below that the clock stays continuous."""
    global _CLOCK_OFFSET_NS
    offset = time.time_ns() - time.perf_counter_ns()
    if abs(offset - _CLOCK_OFFSET_NS) > 1_000_000:
        _CLOCK_OFFSET_NS = offset


class SpanContext:
    __slots__ = ("trace_id", "span_id", "sampled")

    def __init__(self, trace_id: bytes, span_id: bytes, sampled: bool = True):
        self.trace_id = trace_id
        self.span_id = span_id
        self.sampled = sampled


class Span:
    """A mutable in-flight span. Context-manager; ends on __exit__."""

    __slots__ = ("name", "context", "parent_span_id", "kind", "start_ns",
                 "end_ns", "attributes", "events", "status_code",
                 "status_message", "_tracer", "_token", "_cpu_start_ns",
                 "_thread_id")

    def __init__(self, tracer, name: str, context: SpanContext,
                 parent_span_id: bytes | None, kind: int,
                 start_ns: int | None = None,
                 cpu_start_ns: int | None = None):
        self.name = name
        self.context = context
        self.parent_span_id = parent_span_id
        self.kind = kind
        self.start_ns = start_ns or now_ns()
        # `cpu_ns()` of the thread that starts the span, taken beside
        # the start stamp, and that thread (`start_span` below)
        self._cpu_start_ns = cpu_start_ns
        self._thread_id = threading.get_ident()
        self.end_ns = 0
        self.attributes: dict = {}
        self.events: list = []
        self.status_code = STATUS_UNSET
        self.status_message = ""
        self._tracer = tracer
        self._token = None

    @property
    def recording(self) -> bool:
        return True

    def set_attribute(self, key: str, value) -> "Span":
        self.attributes[key] = value
        return self

    def set_attributes(self, **kv) -> "Span":
        self.attributes.update(kv)
        return self

    def add_event(self, name: str, **attributes) -> "Span":
        self.events.append((now_ns(), name, attributes))
        return self

    def set_status(self, code: int, message: str = "") -> "Span":
        self.status_code = code
        self.status_message = message
        return self

    def record_exception(self, exc: BaseException) -> "Span":
        self.add_event("exception",
                       **{"exception.type": type(exc).__name__,
                          "exception.message": str(exc)})
        return self.set_status(STATUS_ERROR, str(exc))

    def end(self, end_ns: int | None = None,
            cpu_end_ns: int | None = None) -> None:
        """end_ns: a `now_ns()` stamp the caller already took at the
        edge this span ends on (a wait that ended on another thread, a
        stage whose histogram sample reads the same stamp);
        cpu_end_ns: the `cpu_ns()` stamp taken beside it.

        A span that has a CPU stamp at both edges, taken on one thread,
        says so: `thread.id`, and `thread.cpu_ns`, the CPU that thread
        burned between them, children included. One ended on another
        thread than started it, or written from wall stamps alone, is a
        wait that crossed threads and carries neither."""
        if self.end_ns:
            return
        # `is not None`: a young thread's CPU clock reads 0 where the
        # kernel accounts CPU by the tick
        on_its_thread = (self._cpu_start_ns is not None
                         and threading.get_ident() == self._thread_id)
        if end_ns:
            self.end_ns = end_ns
        else:
            self.end_ns = now_ns()
            if on_its_thread:
                cpu_end_ns = cpu_ns()
        if on_its_thread and cpu_end_ns is not None:
            self.attributes["thread.id"] = self._thread_id
            self.attributes["thread.cpu_ns"] = (cpu_end_ns
                                                - self._cpu_start_ns)
        if self.context.sampled:
            self._tracer._on_end(self)

    def __enter__(self) -> "Span":
        self._token = _current_span.set(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is not None:
            self.record_exception(exc)
        if self._token is not None:
            _current_span.reset(self._token)
            self._token = None
        self.end()
        return False


class _NoopSpan:
    """Shared, immutable, free — the no-tracer / suppressed path."""

    __slots__ = ()
    recording = False
    context = SpanContext(b"\x00" * 16, b"\x00" * 8, sampled=False)

    def set_attribute(self, key, value):
        return self

    def set_attributes(self, **kv):
        return self

    def add_event(self, name, **attributes):
        return self

    def set_status(self, code, message=""):
        return self

    def record_exception(self, exc):
        return self

    def end(self, end_ns=None, cpu_end_ns=None):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


NOOP_SPAN = _NoopSpan()


class NonRecordingSpan:
    """A sampled-OUT span: records nothing, but *does* become the current
    span so descendants inherit the not-sampled decision instead of
    re-rolling the dice (which would emit orphan mid-stack spans)."""

    __slots__ = ("context", "_token")
    recording = False

    def __init__(self, context: SpanContext):
        self.context = context
        self._token = None

    def set_attribute(self, key, value):
        return self

    def set_attributes(self, **kv):
        return self

    def add_event(self, name, **attributes):
        return self

    def set_status(self, code, message=""):
        return self

    def record_exception(self, exc):
        return self

    def end(self, end_ns=None, cpu_end_ns=None):
        pass

    def __enter__(self):
        self._token = _current_span.set(self)
        return self

    def __exit__(self, *a):
        if self._token is not None:
            _current_span.reset(self._token)
            self._token = None
        return False


class Tracer:
    """Probabilistic-sampling tracer feeding a span processor."""

    def __init__(self, processor, service_name: str = "tempo-tpu",
                 sample_ratio: float = 1.0,
                 instance_id: str | None = None):
        self.processor = processor
        self.service_name = service_name
        self.sample_ratio = sample_ratio
        self.instance_id = instance_id or f"pid-{os.getpid()}"
        self._rng = random.Random()

    def start_span(self, name: str, kind: int = KIND_INTERNAL,
                   parent: SpanContext | None = None,
                   start_ns: int | None = None,
                   cpu_start_ns: int | None = None, **attributes):
        """`start_ns`: a `now_ns()` stamp taken where the spanned work
        began — with `parent=` a context captured there and
        `end(end_ns=)`, a wait that crossed threads is written after
        the fact from its two stamps (`record_span`). `cpu_start_ns`:
        the `cpu_ns()` stamp taken beside `start_ns`, by the thread
        that writes the span."""
        if _suppressed.get():
            return NOOP_SPAN
        cur = _current_span.get()
        if parent is None and cur is not None:
            parent = cur.context
        if parent is not None:
            trace_id, parent_id, sampled = (parent.trace_id, parent.span_id,
                                            parent.sampled)
        else:
            trace_id = self._rng.getrandbits(128).to_bytes(16, "big")
            parent_id = None
            sampled = self._rng.random() < self.sample_ratio
        if not sampled:
            # keep the negative decision on the context stack
            return NonRecordingSpan(SpanContext(trace_id, parent_id
                                                or b"\x00" * 8, False))
        ctx = SpanContext(trace_id,
                          self._rng.getrandbits(64).to_bytes(8, "big"), True)
        span = Span(self, name, ctx, parent_id, kind, start_ns,
                    cpu_start_ns)
        if attributes:
            span.attributes.update(attributes)
        return span

    def _on_end(self, span: Span) -> None:
        self.processor.on_end(span)

    def shutdown(self) -> None:
        self.processor.shutdown()


class BatchProcessor:
    """Buffers finished spans; a daemon thread flushes them to the
    exporter every ``interval_s`` or at ``max_batch`` (reference: OTel
    BatchSpanProcessor role)."""

    def __init__(self, exporter, max_batch: int = 512,
                 max_queue: int = 8192, interval_s: float = 2.0):
        self.exporter = exporter
        self.max_batch = max_batch
        self.interval_s = interval_s
        self._q: queue.Queue = queue.Queue(maxsize=max_queue)
        # dropped-span accounting lives in ONE place — the labeled
        # counter; the instance view derives from it (before, the bare
        # `self.dropped += 1` int and the unlabeled counter could drift,
        # and the counter could not distinguish exporters)
        self._exporter_label = type(exporter).__name__
        from . import metrics as obs

        self._dropped_base = obs.selftrace_dropped_spans.value(
            exporter=self._exporter_label)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="tempo-tpu-trace-export")
        self._thread.start()

    @property
    def dropped(self) -> int:
        """Spans THIS processor dropped: derived from the labeled
        counter (single source of truth) minus the baseline captured at
        construction, so concurrent processors over the same exporter
        class cannot make a fresh one report history it never had."""
        from . import metrics as obs

        return int(obs.selftrace_dropped_spans.value(
            exporter=self._exporter_label) - self._dropped_base)

    def on_end(self, span: Span) -> None:
        try:
            self._q.put_nowait(span)
        except queue.Full:
            # visible, not just instance state: a saturated exporter was
            # previously indistinguishable from a healthy quiet one —
            # and labeled by exporter, like selftrace_export_failures
            from . import metrics as obs

            obs.selftrace_dropped_spans.inc(exporter=self._exporter_label)

    def _drain(self) -> list:
        out = []
        while len(out) < self.max_batch:
            try:
                out.append(self._q.get_nowait())
            except queue.Empty:
                break
        return out

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            self._flush_once()
        self._flush_once()

    def _flush_once(self) -> None:
        while True:
            batch = self._drain()
            if not batch:
                return
            tok = _suppressed.set(True)
            try:
                self.exporter.export(batch)
            except Exception:  # noqa: BLE001 — never kill the loop, but
                # COUNT it: a dead collector endpoint silently eating
                # every batch must show up on /metrics
                from . import metrics as obs

                obs.selftrace_export_failures.inc(
                    exporter=type(self.exporter).__name__)
            finally:
                _suppressed.reset(tok)

    def force_flush(self) -> None:
        self._flush_once()

    def shutdown(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)
        self._flush_once()


class SyncProcessor:
    """Export on end, inline (tests / short-lived CLIs)."""

    def __init__(self, exporter):
        self.exporter = exporter

    def on_end(self, span: Span) -> None:
        tok = _suppressed.set(True)
        try:
            self.exporter.export([span])
        finally:
            _suppressed.reset(tok)

    def force_flush(self) -> None:
        pass

    def shutdown(self) -> None:
        pass


# ---------------------------------------------------------------- export


def _any_value(v):
    from tempo_tpu import tempopb

    av = tempopb.AnyValue()
    if isinstance(v, bool):
        av.bool_value = v
    elif isinstance(v, int):
        av.int_value = v
    elif isinstance(v, float):
        av.double_value = v
    elif isinstance(v, bytes):
        av.bytes_value = v
    else:
        av.string_value = str(v)
    return av


def spans_to_resource_spans(spans: list, service_name: str,
                            instance_id: str):
    """Convert finished Spans → one tempopb.ResourceSpans (OTLP wire)."""
    from tempo_tpu import tempopb

    rs = tempopb.ResourceSpans()
    kv = rs.resource.attributes.add()
    kv.key = "service.name"
    kv.value.string_value = service_name
    kv = rs.resource.attributes.add()
    kv.key = "service.instance.id"
    kv.value.string_value = instance_id
    ss = rs.scope_spans.add()
    ss.scope.name = "tempo_tpu.observability.tracing"
    for s in spans:
        p = ss.spans.add()
        p.trace_id = s.context.trace_id
        p.span_id = s.context.span_id
        if s.parent_span_id:
            p.parent_span_id = s.parent_span_id
        p.name = s.name
        p.kind = s.kind
        p.start_time_unix_nano = s.start_ns
        p.end_time_unix_nano = s.end_ns
        for k, v in s.attributes.items():
            kv = p.attributes.add()
            kv.key = k
            kv.value.CopyFrom(_any_value(v))
        for ts, name, attrs in s.events:
            ev = p.events.add()
            ev.time_unix_nano = ts
            ev.name = name
            for k, v in attrs.items():
                kv = ev.attributes.add()
                kv.key = k
                kv.value.CopyFrom(_any_value(v))
        p.status.code = s.status_code
        if s.status_message:
            p.status.message = s.status_message
    return rs


class SelfExporter:
    """Push the framework's own spans back into the framework — the
    "tempo traces tempo" loop, minus the network: calls
    ``push(tenant, [ResourceSpans])`` (Distributor/App signature)."""

    def __init__(self, push, tenant: str = "self",
                 service_name: str = "tempo-tpu",
                 instance_id: str = "self"):
        self.push = push
        self.tenant = tenant
        self.service_name = service_name
        self.instance_id = instance_id

    def export(self, spans: list) -> None:
        rs = spans_to_resource_spans(spans, self.service_name,
                                     self.instance_id)
        self.push(self.tenant, [rs])


# the dogfood pipeline's reserved tenant: self-trace spans ingested
# through the normal distributor path land here, away from user data.
# A leading underscore passes tenant validation (utils/pathsafe allows
# it) while making the reservation visually obvious in blocklists.
SELFTRACE_TENANT = "_selftrace"


class InProcessExporter(SelfExporter):
    """The dogfood ingest exporter (`selftrace_ingest_enabled`):
    finished self-trace spans become the existing push wire format and
    ride the normal distributor/TenantInstance ingest path into the
    reserved ``_selftrace`` tenant — every search request, device
    dispatch, flush, poll and compaction becomes a real trace queryable
    via trace-by-ID, tag search, structural ``?q=``, ``?agg=`` and live
    tail. The surrounding BatchProcessor/SyncProcessor suppression
    covers the whole ingest-of-self-spans path, so the loop cannot feed
    back (test_self_export_suppression_no_recursion)."""

    def __init__(self, push, service_name: str = "tempo-tpu",
                 instance_id: str = "self"):
        super().__init__(push, tenant=SELFTRACE_TENANT,
                         service_name=service_name,
                         instance_id=instance_id)


class OTLPHTTPExporter:
    """OTLP/HTTP protobuf export to any collector (or another tempo-tpu's
    /v1/traces receiver)."""

    def __init__(self, endpoint: str, tenant: str | None = None,
                 service_name: str = "tempo-tpu",
                 instance_id: str = "self", timeout_s: float = 5.0):
        self.endpoint = endpoint.rstrip("/")
        if not self.endpoint.endswith("/v1/traces"):
            self.endpoint += "/v1/traces"
        self.tenant = tenant
        self.service_name = service_name
        self.instance_id = instance_id
        self.timeout_s = timeout_s

    def export(self, spans: list) -> None:
        from tempo_tpu import tempopb

        rs = spans_to_resource_spans(spans, self.service_name,
                                     self.instance_id)
        trace = tempopb.Trace()
        trace.batches.append(rs)
        req = urllib.request.Request(
            self.endpoint, data=trace.SerializeToString(), method="POST",
            headers={"Content-Type": "application/x-protobuf"})
        if self.tenant:
            req.add_header("X-Scope-OrgID", self.tenant)
        urllib.request.urlopen(req, timeout=self.timeout_s).read()


class CollectExporter:
    """Test exporter: keeps everything."""

    def __init__(self):
        self.spans: list = []
        self.lock = threading.Lock()

    def export(self, spans: list) -> None:
        with self.lock:
            self.spans.extend(spans)


# ----------------------------------------------------------- global state

_tracer: Tracer | None = None


def set_tracer(tracer: Tracer | None) -> None:
    global _tracer
    if tracer is not None:
        _reanchor_clock()
    _tracer = tracer


def get_tracer() -> Tracer | None:
    return _tracer


def start_span(name: str, kind: int = KIND_INTERNAL,
               parent: SpanContext | None = None,
               start_ns: int | None = None,
               cpu_start_ns: int | None = None, **attributes):
    """The one entry the program's span sites use: noop when no tracer
    is installed. A span that takes its own start stamp (`start_ns` not
    given) takes its thread's CPU stamp here too; one written from a
    stamp taken earlier has a CPU stamp only if the call site took one
    there, on this thread, and hands it over."""
    t = _tracer
    if t is None:
        return NOOP_SPAN
    if not start_ns:
        cpu_start_ns = cpu_ns()
    return t.start_span(name, kind=kind, parent=parent, start_ns=start_ns,
                        cpu_start_ns=cpu_start_ns, **attributes)


def record_span(name: str, start_ns: int, end_ns: int,
                parent: SpanContext | None = None,
                cpu_start_ns: int | None = None,
                cpu_end_ns: int | None = None, **attributes) -> None:
    """Write a finished span from two `now_ns()` stamps and, where the
    calling thread took them beside those, two `cpu_ns()` stamps. Call
    sites on hot paths guard on `get_tracer() is not None` (or a
    recording span) BEFORE building the attributes."""
    start_span(name, parent=parent, start_ns=start_ns,
               cpu_start_ns=cpu_start_ns, **attributes).end(end_ns,
                                                            cpu_end_ns)


def current_span():
    s = _current_span.get()
    return s if s is not None else NOOP_SPAN


def force_flush() -> None:
    t = _tracer
    if t is not None:
        t.processor.force_flush()


def init_tracing(cfg: dict, push=None) -> Tracer | None:
    """Build + install a tracer from config::

        self_tracing:
          enabled: true
          exporter: self | otlp        # default self when push given
          endpoint: http://host:3200   # for otlp
          tenant: self
          sample_ratio: 1.0
          service_name: tempo-tpu
          selftrace_ingest_enabled: false   # dogfood pipeline: ingest
                                            # into _selftrace, stage
                                            # child spans, querystats
                                            # span attrs, flight recorder
          selftrace_flight_recorder_max: 32
    """
    cfg = cfg or {}
    # the dogfood gate + flight recorder configure HERE — the one entry
    # point every App/test uses — so gate state always tracks the most
    # recently installed tracer config (the REGISTRY idiom). Tracing
    # disabled forces the gate off: there are no spans to dogfood.
    ingest_on = bool(cfg.get("enabled")) and bool(
        cfg.get("selftrace_ingest_enabled", False))
    from . import selftrace as _selftrace

    _selftrace.configure(
        ingest_enabled=ingest_on,
        flight_recorder_max=int(
            cfg.get("selftrace_flight_recorder_max", 32)))
    if not cfg.get("enabled"):
        return None
    service = cfg.get("service_name", "tempo-tpu")
    tenant = cfg.get("tenant", "self")
    exporter_kind = cfg.get("exporter", "self" if push is not None else "otlp")
    if exporter_kind == "self":
        if push is None:
            raise ValueError("self exporter needs an in-process push target")
        if ingest_on:
            # dogfood pipeline: the reserved tenant wins over any
            # configured one — user tenants must not receive self-spans
            exporter = InProcessExporter(push, service_name=service)
        else:
            exporter = SelfExporter(push, tenant=tenant,
                                    service_name=service)
    elif exporter_kind == "otlp":
        endpoint = cfg.get("endpoint")
        if not endpoint:
            raise ValueError(
                "self_tracing: exporter 'otlp' requires an 'endpoint' "
                "(e.g. http://collector:3200)")
        exporter = OTLPHTTPExporter(endpoint, tenant=tenant,
                                    service_name=service)
    else:
        raise ValueError(f"unknown trace exporter {exporter_kind!r}")
    proc = BatchProcessor(exporter,
                          interval_s=float(cfg.get("flush_interval_s", 2.0)))
    tracer = Tracer(proc, service_name=service,
                    sample_ratio=float(cfg.get("sample_ratio", 1.0)))
    set_tracer(tracer)
    return tracer


# ------------------------------------------------------- W3C propagation


def inject_traceparent(headers: dict) -> dict:
    """Add a `traceparent` header for the active span (outgoing RPC).
    A sampled-out span still injects (flags 00) so downstream processes
    honor the negative decision instead of re-sampling."""
    s = _current_span.get()
    if s is not None and s.context.trace_id != b"\x00" * 16:
        c = s.context
        span_id = c.span_id if s.recording else b"\x00" * 8
        if span_id == b"\x00" * 8:
            # W3C forbids zero parent-id; reuse the trace id tail
            span_id = c.trace_id[8:]
        headers["traceparent"] = (
            f"00-{c.trace_id.hex()}-{span_id.hex()}-"
            f"{'01' if c.sampled else '00'}")
    return headers


def extract_traceparent(headers) -> SpanContext | None:
    """Parse an incoming `traceparent`; returns a remote parent context."""
    try:
        get = headers.get
    except AttributeError:
        return None
    v = get("traceparent") or get("Traceparent")
    if not v:
        return None
    parts = v.strip().split("-")
    if (len(parts) != 4 or len(parts[1]) != 32 or len(parts[2]) != 16
            or len(parts[3]) != 2):
        return None
    try:
        trace_id = bytes.fromhex(parts[1])
        span_id = bytes.fromhex(parts[2])
        sampled = bool(int(parts[3], 16) & 1)
    except ValueError:
        return None
    # W3C: all-zero trace-id or parent-id is invalid
    if trace_id == b"\x00" * 16 or span_id == b"\x00" * 8:
        return None
    return SpanContext(trace_id, span_id, sampled)


# ------------------------------------------------------------ spanlogger


class SpanLogger:
    """Couples a logger to a span: every log line also lands on the span
    as an event, so traces carry their own narration (reference:
    pkg/util/spanlogger)."""

    def __init__(self, name: str, logger: logging.Logger | None = None,
                 tenant: str | None = None, **attributes):
        from .log import get_logger

        self.logger = logger or get_logger()
        self.span = start_span(name, **attributes)
        if tenant is not None:
            self.span.set_attribute("tenant", tenant)
        self.tenant = tenant

    def log(self, msg: str, level: int = logging.DEBUG, **kv) -> None:
        self.span.add_event(msg, **kv)
        if kv:
            msg = msg + " " + " ".join(f"{k}={v}" for k, v in kv.items())
        if self.tenant:
            msg = f"tenant={self.tenant} {msg}"
        self.logger.log(level, msg)

    def error(self, exc: BaseException, msg: str = "") -> None:
        self.span.record_exception(exc)
        self.logger.error("%s: %s", msg or "error", exc)

    def __enter__(self):
        self.span.__enter__()
        return self

    def __exit__(self, *a):
        return self.span.__exit__(*a)
