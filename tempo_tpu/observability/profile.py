"""Device-path dispatch profiler: per-dispatch stage telemetry.

PRs 1-4 (coalescing, HBM tiering, device dict probe) each had to infer
where device time went from bench wall-clocks — there was no first-class
visibility into the stages the TPU lift actually changes. This module
gives every device dispatch path (batched, coalesced, mesh-sharded,
and the dict-probe kernel) a stage breakdown:

  build    host-side predicate/table build (device-param upload prep,
           query-table asarray; `mode=host_probe` records the host
           dictionary prefilter — PR4's motivating cost)
  h2d      host→device staging puts (bytes counted separately)
  compile  the dispatch call when the jit cache missed for this shape
           signature — tracing + XLA compile dominate that call
  execute  the dispatch call on a cache hit, plus the
           ``block_until_ready`` fence that attributes true kernel time
  d2h      device→host result fetch / fused-group demux
  lock_wait  time queued on the process-wide collective dispatch lock
           (parallel.mesh.dispatch_lock) — mesh paths only

Records land in a bounded ring buffer (``/debug/profile`` renders the
recent ones) and aggregate into metrics:

  tempo_search_dispatch_stage_seconds{stage,mode}   (histogram)
  tempo_search_jit_cache_events_total{result}       (counter)
  tempo_search_h2d_bytes_total / tempo_search_d2h_bytes_total

Each timed stage also becomes a `dispatch.<stage>` child of the active
self-trace span, with the interval the stage timer observed, so a slow
query's own trace shows which stage ate the time and when. While a
tracer is installed, DEVICE_TIMELINE adds the device's side: one
`device.scan` span per kernel launch, from a watcher thread that waits
on the launches' outputs in launch order (no fence on the dispatch
path).

Design constraints (mirrors tracing.py's noop stance):
- A TRUE noop path: with profiling disabled every call site pays one
  attribute check and gets back a shared immutable noop object — no
  allocation, no clock reads, no lock. `search_profiling_enabled: false`
  must cost nothing measurable on the dispatch hot path.
- Jit-compile detection needs no jax internals: the profiler keeps its
  own bounded set of shape signatures per dispatch site; a first-seen
  signature is a compile-cache miss (jit caches key on exactly these
  statics — the call sites pass the same tuple the kernel's
  static_argnames + array shapes/dtypes imply).
- The ``execute`` fence (``block_until_ready`` after the dispatch call)
  attributes TRUE kernel time, but converts the async enqueue into a
  synchronous wait — which breaks the batcher's dispatch/drain
  pipelining. It is therefore OPT-IN (``search_profiling_fence``,
  default off): unfenced, "execute" measures the dispatch call (enqueue
  + any synchronous work) and the device wait lands in the "d2h" stage
  at the sync point, which still answers "which stage ate the time" at
  dispatch granularity. Bench phase ``profile_overhead`` re-measures
  the enabled-vs-disabled delta every round; the noop path is the <2%
  contract.
"""

from __future__ import annotations

import contextlib
import itertools
import queue
import threading
import time
from collections import deque

from . import metrics as obs
from . import tracing
from .log import get_logger

log = get_logger("tempo_tpu.profile")

STAGES = ("build", "h2d", "compile", "execute", "d2h", "lock_wait")

# per-thread stack of record sinks (collect_records): a dispatch record
# finishing on this thread is ALSO handed to the innermost open
# collector. Thread-local rather than a contextvar: dispatch + close
# always happen on the thread that ran the engine call, and the
# coalescer's flush threads must not inherit a submitter's collector.
_collect_local = threading.local()


@contextlib.contextmanager
def collect_records():
    """Collect the dispatch records (as_dict form) finished on THIS
    thread inside the body — the query-stats attribution hook: the
    caller apportions the record's stages to the query (or queries)
    the dispatch served. Nests; profiling disabled yields no records
    (the noop dispatch never finishes)."""
    stack = getattr(_collect_local, "stack", None)
    if stack is None:
        stack = _collect_local.stack = []
    recs: list[dict] = []
    stack.append(recs)
    try:
        yield recs
    finally:
        stack.pop()

_COMPILE_SEEN_MAX = 4096  # shape signatures tracked before reset


class _NoopStage:
    """Shared, immutable, free — the disabled-profiler stage context."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NOOP_STAGE = _NoopStage()


class _NoopDispatch:
    """Shared noop dispatch record: every method is a cheap no-op so the
    call sites never branch on `enabled` themselves."""

    __slots__ = ()
    enabled = False
    intervals = None

    def stage(self, name):
        return _NOOP_STAGE

    def add_stage(self, name, seconds):
        return self

    def add_interval(self, name, start_ns, end_ns, cpu_start_ns=None,
                     cpu_end_ns=None):
        return self

    def add_bytes(self, h2d=0, d2h=0):
        return self

    def compile_check(self, key) -> bool:
        return False

    def fence(self, arrays):
        return self

    def set(self, **kv):
        return self

    def close(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()
        return False


NOOP_DISPATCH = _NoopDispatch()


class _StageTimer:
    __slots__ = ("_rec", "_name", "_t0", "_c0")

    def __init__(self, rec, name):
        self._rec = rec
        self._name = name

    def __enter__(self):
        self._t0 = tracing.now_ns()
        # the thread's CPU clock beside it, only where the interval
        # will become a span
        self._c0 = (tracing.cpu_ns() if self._rec.intervals is not None
                    else None)
        return self

    def __exit__(self, *a):
        t1 = tracing.now_ns()
        c1 = tracing.cpu_ns() if self._rec.intervals is not None else None
        self._rec.add_interval(self._name, self._t0, t1, self._c0, c1)
        return False


class Dispatch:
    """One in-flight dispatch's profile record. Context-manager; the
    record is published (ring + metrics + span event) on close()."""

    __slots__ = ("mode", "stages", "intervals", "h2d_bytes", "d2h_bytes",
                 "jit", "jit_key", "attrs", "t0", "_prof", "_closed")
    enabled = True

    def __init__(self, prof, mode: str):
        self.mode = mode
        self.stages: dict[str, float] = {}
        # (stage, start_ns, end_ns, cpu_start_ns, cpu_end_ns) as the
        # stage timers read them (the CPU stamps None where the site
        # took none), kept only while a tracer is installed: _finish writes
        # them as `dispatch.<stage>` spans
        self.intervals = [] if tracing.get_tracer() is not None else None
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.jit = None       # None (no kernel), "hit" or "miss"
        self.jit_key = None   # the shape signature compile_check saw
        self.attrs: dict = {}
        self.t0 = time.perf_counter()
        self._prof = prof
        self._closed = False

    def stage(self, name: str) -> _StageTimer:
        return _StageTimer(self, name)

    def add_stage(self, name: str, seconds: float) -> "Dispatch":
        self.stages[name] = self.stages.get(name, 0.0) + seconds
        return self

    def add_interval(self, name: str, start_ns: int, end_ns: int,
                     cpu_start_ns: int | None = None,
                     cpu_end_ns: int | None = None) -> "Dispatch":
        """A stage from two `tracing.now_ns()` stamps: its seconds and
        its span are the same two clock reads. The two `cpu_ns()`
        stamps, taken beside them while `intervals` is kept, go to the
        span alone."""
        self.add_stage(name, (end_ns - start_ns) / 1e9)
        if self.intervals is not None:
            self.intervals.append((name, start_ns, end_ns, cpu_start_ns,
                                   cpu_end_ns))
        return self

    def add_bytes(self, h2d: int = 0, d2h: int = 0) -> "Dispatch":
        self.h2d_bytes += int(h2d)
        self.d2h_bytes += int(d2h)
        return self

    def compile_check(self, key) -> bool:
        """First sighting of this shape signature = jit cache miss. The
        caller times the dispatch call under stage "compile" on a miss
        (tracing + XLA compile dominate it) and "execute" on a hit."""
        miss = self._prof._compile_miss(key)
        self.jit = "miss" if miss else "hit"
        self.jit_key = key
        return miss

    def fence(self, arrays) -> "Dispatch":
        """block_until_ready the kernel outputs when the profiler's
        fence is on — called inside the "execute" stage so kernel time
        is attributed there instead of at the later sync point."""
        if self._prof.fence:
            fence_arrays(arrays)
        return self

    def set(self, **kv) -> "Dispatch":
        self.attrs.update(kv)
        return self

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._prof._finish(self)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()
        return False

    def as_dict(self) -> dict:
        d = {
            "mode": self.mode,
            "stages_ms": {k: round(v * 1e3, 3)
                          for k, v in self.stages.items()},
            "total_ms": round(sum(self.stages.values()) * 1e3, 3),
        }
        if self.h2d_bytes:
            d["h2d_bytes"] = self.h2d_bytes
        if self.d2h_bytes:
            d["d2h_bytes"] = self.d2h_bytes
        if self.jit is not None:
            d["jit_cache"] = self.jit
        if self.attrs:
            d["attrs"] = self.attrs
        return d


class DispatchProfiler:
    """Process-wide profiler (module singleton ``PROFILER``, the
    REGISTRY idiom): config flips ``enabled``; dispatch sites call
    ``dispatch(mode)`` and get either a recording ``Dispatch`` or the
    shared noop."""

    def __init__(self, ring_size: int = 256, enabled: bool = True,
                 fence: bool = False):
        self.enabled = enabled
        # fence=True adds a block_until_ready after each profiled kernel
        # call (true kernel-time attribution, at the cost of the async
        # dispatch pipelining — see module docstring)
        self.fence = fence
        self._ring: deque = deque(maxlen=ring_size)
        self._lock = threading.Lock()
        self._compile_seen: set = set()
        # aggregates over the process lifetime (cheap dict sums — the
        # histogram has the full distribution, this answers /debug/profile
        # without a metrics scrape); values are [n, total_s, total_bytes]
        # so byte-carrying stages expose a replayable rate (the offload
        # planner's offline calibration, scripts/calibrate_offload.py)
        self._agg: dict[tuple, list] = {}   # (mode, stage) -> [n, s, bytes]
        self._jit = {"hit": 0, "miss": 0}
        self._bytes = {"h2d": 0, "d2h": 0}
        self._dispatches = 0
        # consumers of finished records / stage observations (the offload
        # planner's live feed, search/planner.py) — called OUTSIDE the
        # lock, exceptions swallowed, only when profiling is enabled
        self._listeners: list = []
        self._stage_listeners: list = []
        # unix time of the last successfully finished dispatch/stage —
        # the /status device block's "is the chip still answering"
        # signal (None until the first device op of the process)
        self.last_dispatch_t: float | None = None

    # ---- call-site API ----

    def dispatch(self, mode: str):
        # liveness stamp even when profiling is off: /status's
        # wedge-vs-idle signal (device_status) must not depend on the
        # profiling knob — one coarse clock read; the noop contract's
        # no-locks/no-allocation still holds and the record protocol
        # itself stays free
        self.last_dispatch_t = time.time()
        if not self.enabled:
            return NOOP_DISPATCH
        return Dispatch(self, mode)

    def add_listener(self, fn) -> None:
        """Subscribe to finished dispatch records (called with the
        record's as_dict form). The offload planner's live feed."""
        with self._lock:
            if fn not in self._listeners:
                self._listeners.append(fn)

    def add_stage_listener(self, fn) -> None:
        """Subscribe to out-of-record stage observations; called with
        (stage, mode, seconds, nbytes)."""
        with self._lock:
            if fn not in self._stage_listeners:
                self._stage_listeners.append(fn)

    def observe_stage(self, stage: str, mode: str, seconds: float,
                      nbytes: int = 0, spanned: bool = False) -> None:
        """Record one stage observation outside a dispatch record (e.g.
        staging H2D that serves many later dispatches, or the drain-side
        D2H fetch). Noop when disabled. `nbytes` feeds the transfer
        counters only for the transfer stages; other stages (the host
        prefilter's scanned bytes) keep it in the aggregates alone.
        `spanned`: the caller wrote a span from the same two stamps, so
        the trace gets no `profile.stage` event beside it."""
        # liveness stamp (see dispatch()) — but NOT for host-only work:
        # mode=host_probe runs with the device wedged just fine, and a
        # fresh last_dispatch_age_s fed by host scans would mask exactly
        # the wedge the /status device block exists to expose
        if mode != "host_probe":
            self.last_dispatch_t = time.time()
        if not self.enabled:
            return
        obs.dispatch_stage_seconds.observe(seconds, stage=stage, mode=mode)
        transfer = stage in ("h2d", "d2h")
        with self._lock:
            k = (mode, stage)
            a = self._agg.get(k)
            if a is None:
                a = self._agg[k] = [0, 0.0, 0]
            a[0] += 1
            a[1] += seconds
            a[2] += nbytes
            if nbytes and transfer:
                self._bytes[stage] += nbytes
        if nbytes and transfer:
            (obs.h2d_bytes if stage == "h2d" else obs.d2h_bytes).inc(nbytes)
        for fn in self._stage_listeners:
            try:
                fn(stage, mode, seconds, nbytes)
            except Exception:  # noqa: BLE001 — listeners never fail a scan
                pass
        if spanned:
            return
        span = tracing.current_span()
        if span.recording:
            span.add_event("profile.stage", stage=stage, mode=mode,
                           ms=round(seconds * 1e3, 3))

    # ---- internals ----

    def seen(self, key) -> bool:
        """Whether this shape signature has been dispatched before —
        WITHOUT recording it. The offload planner uses this to predict
        whether a device decision would pay an XLA compile."""
        with self._lock:
            return key in self._compile_seen

    def _compile_miss(self, key) -> bool:
        with self._lock:
            if key in self._compile_seen:
                miss = False
            else:
                if len(self._compile_seen) >= _COMPILE_SEEN_MAX:
                    self._compile_seen.clear()
                self._compile_seen.add(key)
                miss = True
        obs.jit_cache_events.inc(result="miss" if miss else "hit")
        return miss

    def _finish(self, rec: Dispatch) -> None:
        for stage, sec in rec.stages.items():
            obs.dispatch_stage_seconds.observe(sec, stage=stage,
                                               mode=rec.mode)
        if rec.h2d_bytes:
            obs.h2d_bytes.inc(rec.h2d_bytes)
        if rec.d2h_bytes:
            obs.d2h_bytes.inc(rec.d2h_bytes)
        if rec.jit == "miss":
            # one line per first-seen shape signature: which jit key
            # compiled and how long that dispatch call took — cold start
            # is the sum of these
            log.info("jit compile: mode=%s compile_ms=%.1f key=%r",
                     rec.mode, rec.stages.get("compile", 0.0) * 1e3,
                     rec.jit_key)
        rd = rec.as_dict()
        stack = getattr(_collect_local, "stack", None)
        if stack:
            stack[-1].append(rd)
        with self._lock:
            self._dispatches += 1
            self.last_dispatch_t = time.time()
            if rec.jit is not None:
                self._jit[rec.jit] += 1
            self._bytes["h2d"] += rec.h2d_bytes
            self._bytes["d2h"] += rec.d2h_bytes
            for stage, sec in rec.stages.items():
                k = (rec.mode, stage)
                a = self._agg.get(k)
                if a is None:
                    a = self._agg[k] = [0, 0.0, 0]
                a[0] += 1
                a[1] += sec
                if stage == "h2d":
                    a[2] += rec.h2d_bytes
                elif stage == "d2h":
                    a[2] += rec.d2h_bytes
            self._ring.append(rd)
        for fn in self._listeners:
            try:
                fn(rd)
            except Exception:  # noqa: BLE001 — listeners never fail a scan
                pass
        if rec.intervals:
            span = tracing.current_span()
            if span.recording:
                self._write_stage_spans(rec, span.context)

    @staticmethod
    def _write_stage_spans(rec: Dispatch, parent) -> None:
        """`dispatch.<stage>` children of the span that was active when
        the dispatch closed, each with the interval its stage timer
        observed. A stage that was only given a duration (add_stage)
        has no span: a start and end nobody read from the clock would
        be worse than none on a timeline shared with the device's."""
        for stage, start_ns, end_ns, cpu0, cpu1 in rec.intervals:
            span = tracing.start_span(f"dispatch.{stage}", parent=parent,
                                      start_ns=start_ns, cpu_start_ns=cpu0,
                                      stage=stage, mode=rec.mode)
            if stage == "h2d" and rec.h2d_bytes:
                span.set_attribute("bytes", rec.h2d_bytes)
            elif stage == "d2h" and rec.d2h_bytes:
                span.set_attribute("bytes", rec.d2h_bytes)
            if stage in ("compile", "execute") and rec.jit is not None:
                span.set_attribute("jit_cache", rec.jit)
                for key in ("topk", "shards", "pages_per_shard", "params",
                            "membership", "compare", "blocks",
                            "blocks_bucket", "rel", "join_scans",
                            "span_rows", "span_tile", "agg_keys"):
                    if key in rec.attrs:
                        span.set_attribute(key, rec.attrs[key])
            span.end(end_ns, cpu1)

    # ---- operator surface ----

    def snapshot(self, recent: int = 32) -> dict:
        """/debug/profile payload: recent dispatches + aggregates."""
        with self._lock:
            ring = list(self._ring)[-recent:] if recent > 0 else []
            agg = {}
            for (mode, stage), (n, total, nbytes) in sorted(
                    self._agg.items()):
                entry = {
                    "count": n,
                    "total_ms": round(total * 1e3, 3),
                    "mean_ms": round(total / n * 1e3, 3),
                }
                if nbytes:
                    entry["bytes"] = nbytes
                agg.setdefault(mode, {})[stage] = entry
            return {
                "enabled": self.enabled,
                "dispatches": self._dispatches,
                "jit_cache": dict(self._jit),
                "bytes": dict(self._bytes),
                "aggregates": agg,
                "recent": ring,
            }

    def reset(self) -> None:
        """Test/bench hook: clear ring + aggregates (metrics counters
        are process-lifetime and stay)."""
        with self._lock:
            self._ring.clear()
            self._agg.clear()
            self._compile_seen.clear()
            self._jit = {"hit": 0, "miss": 0}
            self._bytes = {"h2d": 0, "d2h": 0}
            self._dispatches = 0


PROFILER = DispatchProfiler()


class DeviceTimeline:
    """The device's timeline as the program can see it, on the span
    clock: one `device.scan` span per kernel launch.

    Launch sites hand their outputs over right after the enqueue
    (`watch`, only while a tracer is installed); one watcher thread
    takes them in that order and waits on each (`block_until_ready`
    releases the GIL). The device runs launches in enqueue order, so a
    launch ran from the later of (its enqueue, the previous launch's
    outputs ready) to its own outputs ready. Nothing is fenced on the
    dispatch path: the batcher's dispatch/drain pipelining stays.

    Limits: the watcher needs the GIL to stamp, so while Python is busy
    elsewhere a completion is stamped up to a switch interval late and
    busy time is over-read there; two threads that enqueue at the same
    moment may hand over in the other order, which moves time between
    their two spans and leaves the union as it was."""

    IDLE_EXIT_S = 1.0
    # launches queued for the watcher at most: each pins its output
    # arrays on the device until it is taken. The device runs some
    # tens deep at most, so a queue this long means the watcher is
    # stuck behind a launch that never completes; further launches
    # are then left off the timeline and counted instead
    MAX_QUEUED = 1024

    def __init__(self) -> None:
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._lock = threading.Lock()
        self._thread: threading.Thread | None = None
        self._ids = itertools.count(1)

    def watch(self, out, parent, queries: int, blocks: int,
              kernel: str) -> int:
        """Queue one launch's outputs; returns its launch id (the join
        key `coalescer.wait` / `coalescer.launch` carry). `parent` is
        the SpanContext the `device.scan` span hangs under."""
        launch = next(self._ids)
        with self._lock:
            if self._q.qsize() >= self.MAX_QUEUED:
                obs.device_timeline_dropped.inc()
                return launch
            self._q.put((launch, tracing.now_ns(), out, parent, queries,
                         blocks, kernel))
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name="device-timeline")
                self._thread.start()
        return launch

    def running(self) -> bool:
        with self._lock:
            return self._thread is not None

    def _run(self) -> None:
        prev_ready = 0
        try:
            while True:
                try:
                    item = self._q.get(timeout=self.IDLE_EXIT_S)
                except queue.Empty:
                    with self._lock:
                        # the thread lives only while a tracer is installed
                        if (tracing.get_tracer() is None
                                and self._q.empty()):
                            self._thread = None
                            return
                    continue
                launch, enqueued, out, parent, queries, blocks, kernel = item
                fence_arrays(out)
                ready = tracing.now_ns()
                try:
                    if tracing.get_tracer() is not None:
                        tracing.record_span(
                            "device.scan", max(enqueued, prev_ready), ready,
                            parent=parent, launch=launch, queries=queries,
                            blocks=blocks, kernel=kernel)
                except Exception:  # noqa: BLE001 — an exporter that
                    # raises loses this launch's span, not the watcher
                    obs.device_timeline_dropped.inc()
                    log.exception("device.scan span of launch %d lost",
                                  launch)
                prev_ready = ready
        finally:
            # whatever ended this thread, the next `watch` starts
            # another (unless one already has)
            with self._lock:
                if self._thread is threading.current_thread():
                    self._thread = None


DEVICE_TIMELINE = DeviceTimeline()


def configure(enabled: bool | None = None, fence: bool | None = None,
              ring_size: int | None = None) -> DispatchProfiler:
    """Apply config (TempoDBConfig.search_profiling_enabled) to the
    process profiler. Ring resize preserves nothing (the ring is
    diagnostics, not state)."""
    if enabled is not None:
        PROFILER.enabled = bool(enabled)
    if fence is not None:
        PROFILER.fence = bool(fence)
    if ring_size is not None:
        with PROFILER._lock:
            PROFILER._ring = deque(PROFILER._ring, maxlen=int(ring_size))
    return PROFILER


_persist_watch_registered = False


def watch_persistent_compile_cache() -> None:
    """Register a jax.monitoring listener that books every persistent-
    compilation-cache HIT as jit_cache_events{result=persisted} — the
    operator-visible proof that a cold process is replaying first-seen-
    shape compiles from disk (utils.jaxenv.enable_compile_cache wires
    the cache itself). Idempotent."""
    global _persist_watch_registered
    if _persist_watch_registered:
        return
    from jax import monitoring as _monitoring

    def _on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            obs.jit_cache_events.inc(result="persisted")

    _monitoring.register_event_listener(_on_event)
    _persist_watch_registered = True


def dispatch(mode: str):
    """Module-level convenience mirroring tracing.start_span."""
    return PROFILER.dispatch(mode)


def observe_stage(stage: str, mode: str, seconds: float,
                  nbytes: int = 0, spanned: bool = False) -> None:
    PROFILER.observe_stage(stage, mode, seconds, nbytes=nbytes,
                           spanned=spanned)


def build_info() -> dict:
    """Build/runtime identity: package version, jax version, backend,
    native-.so state. Feeds the `tempo_build_info` gauge labels (set
    once at App init) and the /status "build" block (re-evaluated per
    probe). Shares device_status's stance: NEVER initializes a jax
    backend, never triggers a native build — reporting identity must
    not claim a chip or fork a compiler."""
    import os

    import tempo_tpu

    info: dict = {"version": tempo_tpu.__version__}
    try:
        import jax

        info["jax"] = jax.__version__
    except Exception:  # noqa: BLE001 — identity, never fatal
        info["jax"] = "absent"
    try:
        if _backend_initialized():
            import jax

            info["backend"] = jax.default_backend()
        else:
            info["backend"] = "uninitialized"
    except Exception:  # noqa: BLE001 — identity, never fatal (no jax)
        info["backend"] = "unknown"
    try:
        from tempo_tpu.ops import native as _native

        if _native._TRIED:
            info["native"] = ("loaded" if _native._LIB is not None
                              else "absent")
        else:
            # not probed yet: report file presence without loading —
            # _load() may BUILD the .so, and /metrics must not
            info["native"] = ("present" if any(
                os.path.exists(os.path.abspath(p))
                for p in _native._SO_PATHS) else "absent")
    except Exception:  # noqa: BLE001
        info["native"] = "unknown"
    return info


def device_status() -> dict:
    """The /status "device" block: accelerator backend, device kind and
    count, per-device memory as the runtime reports it (WITHOUT
    initializing a backend — write-only processes must never claim a
    chip for a status probe) and the age of the last successful
    dispatch, the operator's first hung-vs-idle signal."""
    out: dict = {
        "dispatches": PROFILER._dispatches,
        "profiling_enabled": PROFILER.enabled,
    }
    t = PROFILER.last_dispatch_t
    out["last_dispatch_age_s"] = (round(time.time() - t, 3)
                                  if t is not None else None)
    try:
        # the circuit breaker's verdict IS the hung-device signal:
        # /status reads this instead of ad-hoc probing
        # (tempo_tpu/robustness/breaker.py)
        from tempo_tpu.robustness import BREAKER

        out["breaker"] = BREAKER.snapshot()
        out["wedged"] = BREAKER.blocking()
    except Exception:  # noqa: BLE001 — status must never 500
        pass
    if not _backend_initialized():
        out["backend"] = "uninitialized"
        return out
    try:
        import jax

        devs = jax.devices()
        out["backend"] = jax.default_backend()
        out["device_count"] = len(devs)
        out["device_kind"] = devs[0].device_kind
        out["devices"] = [_device_memory(d) for d in jax.local_devices()]
    except Exception as e:  # noqa: BLE001 — a dead device must not 500 /status
        out["backend"] = "error"
        out["error"] = f"{type(e).__name__}: {e}"
    return out


def _backend_initialized() -> bool:
    """Whether this process already holds a JAX backend. It looks and
    never probes: jax.default_backend() would INITIALIZE one, and on TPU
    that claims the chip out from under the serving process."""
    from jax._src import xla_bridge

    return bool(xla_bridge._backends)


def _device_memory(d) -> dict:
    """One device's allocator view. `memory_stats()` is None on backends
    that keep no allocator stats (CPU); the keys are then absent rather
    than zero, so a reader cannot mistake "not reported" for "empty"."""
    out = {"id": d.id}
    stats = d.memory_stats() or {}
    for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        if k in stats:
            out[k] = int(stats[k])
    return out


def fence_arrays(arrays) -> None:
    """block_until_ready a kernel's output — the execute-stage fence:
    the scan program's one array, or every device array of another
    kernel's tuple. Tolerates host scalars and None leaves so call
    sites can pass kernel outputs verbatim."""
    if hasattr(arrays, "block_until_ready"):
        arrays = (arrays,)
    for a in arrays:
        wait = getattr(a, "block_until_ready", None)
        if wait is not None:
            try:
                wait()
            except Exception:  # noqa: BLE001 — profiling must never fail a scan
                pass
