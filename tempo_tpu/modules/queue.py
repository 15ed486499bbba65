"""Per-tenant fair request queue + keyed-exclusive flush queues.

Role-equivalent to the reference's pkg/scheduler/queue (frontend v1
per-tenant FIFO fairness with max-outstanding 429s, user_queues.go) and
pkg/flushqueues (priority queues that dedupe in-flight ops,
exclusivequeues.go:10-83).
"""

from __future__ import annotations

import concurrent.futures
import contextvars
import heapq
import itertools
import threading
import time
from collections import OrderedDict, deque

from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import tracing


class TooManyRequests(Exception):
    """Queue full for tenant (reference: HTTP 429)."""


class RequestQueue:
    """Round-robin across tenants, FIFO within a tenant. `get` blocks until
    a request is available or the queue stops.

    The outstanding cap counts top-level REQUESTS (begin_request /
    end_request brackets). This is a DELIBERATE divergence from the
    reference v1 queue, whose MaxOutstandingPerTenant bounds queued
    queue ITEMS — each sharded sub-request individually, which is why
    Tempo's default is as high as 2000 (v1/frontend.go:46-48). Counting
    sub-requests here would make any single search whose own fan-out
    exceeds the cap deterministically 429 itself even on an idle
    system; counting whole requests keeps admission meaningful, so the
    default is 64 concurrent requests per tenant (each fanning out to
    hundreds of sub-requests), with max_queued_per_tenant as the
    complementary memory bound on total queued sub-requests."""

    def __init__(self, max_outstanding_per_tenant: int = 64,
                 max_queued_per_tenant: int = 100_000,
                 filtered_consumers: bool = False):
        self.max_outstanding = max_outstanding_per_tenant
        # memory backpressure, complementary to the request cap: many
        # outstanding requests × many sub-requests each must not grow the
        # queue without bound
        self.max_queued = max_queued_per_tenant
        # filtered_consumers: consumers pass accept predicates (querier
        # shuffle-shard) — a single notify could land on an ineligible
        # consumer and strand the item, so enqueue must wake everyone.
        # Without filters, single notify keeps the hot path O(1)
        self._filtered = filtered_consumers
        self._queues: OrderedDict[str, deque] = OrderedDict()
        self._outstanding: dict[str, int] = {}
        self._cv = threading.Condition()
        self._stopped = False

    def begin_request(self, tenant: str) -> None:
        """Claim an outstanding-request slot; raises TooManyRequests when
        the tenant is at its cap."""
        with self._cv:
            if self._outstanding.get(tenant, 0) >= self.max_outstanding:
                raise TooManyRequests(tenant)
            self._outstanding[tenant] = self._outstanding.get(tenant, 0) + 1

    def end_request(self, tenant: str) -> None:
        with self._cv:
            n = self._outstanding.get(tenant, 1) - 1
            if n > 0:
                self._outstanding[tenant] = n
            else:
                self._outstanding.pop(tenant, None)

    def outstanding(self, tenant: str) -> int:
        with self._cv:
            return self._outstanding.get(tenant, 0)

    def enqueue(self, tenant: str, request) -> None:
        with self._cv:
            if self._stopped:
                raise RuntimeError("queue stopped")
            q = self._queues.get(tenant)
            if q is None:
                q = self._queues[tenant] = deque()
            if len(q) >= self.max_queued:
                raise TooManyRequests(f"{tenant}: sub-request queue full")
            q.append(request)
            if self._filtered:
                self._cv.notify_all()
            else:
                self._cv.notify()

    def get(self, timeout: float | None = None, accept=None):
        """(tenant, request) or None on stop/timeout. Tenants are served
        round-robin: the tenant we serve moves to the back. `accept` is
        an optional tenant predicate — the pull dispatcher's querier
        shuffle-sharding (a worker only drains tenants it is eligible
        for); ineligible tenants stay queued for an eligible consumer."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while True:
                for tenant in list(self._queues):
                    if accept is not None and not accept(tenant):
                        continue
                    q = self._queues[tenant]
                    if q:
                        req = q.popleft()
                        self._queues.move_to_end(tenant)
                        if not q:
                            del self._queues[tenant]
                        return tenant, req
                if self._stopped:
                    return None
                # absolute deadline, not a fresh window per wakeup: with
                # filtered consumers every enqueue wakes everyone, and a
                # per-wait timeout would never elapse under steady
                # traffic — the caller's poll loop (and its
                # is-stream-alive check) must run on schedule
                if deadline is None:
                    self._cv.wait()
                    continue
                left = deadline - time.monotonic()
                if left <= 0:
                    return None
                self._cv.wait(left)

    def lengths(self) -> dict[str, int]:
        with self._cv:
            return {t: len(q) for t, q in self._queues.items()}

    def kick(self) -> None:
        """Wake every blocked consumer so accept predicates re-evaluate —
        called when ELIGIBILITY changed without an enqueue (a worker
        died and survivors inherited its tenants)."""
        with self._cv:
            self._cv.notify_all()

    def stop(self) -> None:
        with self._cv:
            self._stopped = True
            self._cv.notify_all()


class QueueWorkerPool:
    """N workers draining a RequestQueue — the in-process collapse of the
    reference's frontend-v1 fair queue + querier worker fleet
    (v1/frontend.go:33-60, querier/worker): every frontend sub-request
    enqueues under its tenant, workers serve tenants round-robin so a
    noisy tenant cannot starve the rest, and a tenant at its
    outstanding-REQUEST cap (or the sub-request memory bound) is
    rejected with TooManyRequests (HTTP 429)."""

    def __init__(self, workers: int = 50,
                 max_outstanding_per_tenant: int = 64,
                 max_queued_per_tenant: int = 100_000):
        self.queue = RequestQueue(max_outstanding_per_tenant,
                                  max_queued_per_tenant)
        self._n = max(1, workers)
        self._threads: list[threading.Thread] = []
        self._start_lock = threading.Lock()

    def _ensure_started(self) -> None:
        with self._start_lock:
            if self._threads:
                return
            for i in range(self._n):
                t = threading.Thread(target=self._worker, daemon=True,
                                     name=f"query-worker-{i}")
                t.start()
                self._threads.append(t)

    def _worker(self) -> None:
        while True:
            item = self.queue.get()
            if item is None:
                return  # stopped
            tenant, (fut, fn, ctx, stop_event, enqueued, parent, depth) = item
            # the queue wait, enqueue -> a worker has the job: the
            # histogram sample and the span are the same two stamps
            started = tracing.now_ns()
            obs.frontend_queue_duration.observe((started - enqueued) / 1e9)
            if parent is not None and tracing.get_tracer() is not None:
                tracing.record_span(
                    "frontend.queue_wait", enqueued, started, parent=parent,
                    tenant=tenant, depth=depth)
            if not fut.set_running_or_notify_cancel():
                continue
            if stop_event is not None and stop_event.is_set():
                fut.set_result(None)  # request already satisfied (early quit)
                continue
            try:
                fut.set_result(ctx.copy().run(fn))
            except BaseException as e:  # noqa: BLE001 — delivered via future
                fut.set_exception(e)

    def submit(self, tenant: str, fn, stop_event=None,
               ctx: contextvars.Context | None = None) -> concurrent.futures.Future:
        """Enqueue one sub-request. Admission control is request-level
        (begin_request, used by run_jobs); this only rejects —
        TooManyRequests — at the sub-request memory bound."""
        self._ensure_started()
        fut: concurrent.futures.Future = concurrent.futures.Future()
        ctx = ctx if ctx is not None else contextvars.copy_context()
        # with a tracer: the submitter's span, which the wait hangs
        # under, and the tenant's queue length as this job found it
        parent, depth = None, 0
        if tracing.get_tracer() is not None:
            parent = tracing.current_span().context
            depth = self.queue.lengths().get(tenant, 0)
        self.queue.enqueue(tenant, (fut, fn, ctx, stop_event,
                                    tracing.now_ns(), parent, depth))
        return fut

    def run_jobs(self, tenant: str, jobs, fn, stop_event=None):
        """Fan `jobs` through the fair queue as ONE outstanding request
        and gather like db.pool run_jobs: (non-None results, errors). A
        tenant at max_outstanding REQUESTS fails whole with
        TooManyRequests (HTTP 429), before any sub-request enqueues.
        Jobs run under a copy of the caller's contextvars context so the
        active tracing span parents the per-job spans."""
        self.queue.begin_request(tenant)  # raises TooManyRequests at cap
        try:
            ctx = contextvars.copy_context()
            futs = []
            try:
                for j in jobs:
                    futs.append(self.submit(
                        tenant, (lambda j=j: fn(j)),
                        stop_event=stop_event, ctx=ctx))
            except TooManyRequests:
                # sub-request memory bound mid-request: withdraw and fail
                # whole (cancelled corpses drain fast; the bound already
                # capped their memory)
                for f in futs:
                    f.cancel()
                raise
            results, errors = [], []
            for f in futs:
                try:
                    r = f.result()
                except concurrent.futures.CancelledError:
                    continue
                except Exception as e:  # noqa: BLE001 — partial results
                    # every swallowed sub-request is a visibly degraded
                    # answer, not a silent one (the caller decides
                    # whether tolerance lets the response go out).
                    # DeadlineExceeded is booked ONCE by the frontend
                    # under reason=deadline — counting it here too
                    # would double-bill the same event.
                    from tempo_tpu.robustness import DeadlineExceeded

                    if not isinstance(e, DeadlineExceeded):
                        obs.partial_results.inc(reason="subrequest")
                    errors.append(e)
                    continue
                if r is not None:
                    results.append(r)
            return results, errors
        finally:
            self.queue.end_request(tenant)

    def lengths(self) -> dict[str, int]:
        return self.queue.lengths()

    def stop(self) -> None:
        self.queue.stop()


class ExclusiveQueue:
    """Priority queue that refuses duplicate keys while an op is queued or
    in flight — the ingester flush-op dedupe (reference flushqueues)."""

    def __init__(self):
        self._heap: list = []
        self._keys: set = set()
        self._lock = threading.Lock()
        self._counter = itertools.count()

    def enqueue(self, key, priority: float, item) -> bool:
        """False if the key is already queued/in-flight."""
        with self._lock:
            if key in self._keys:
                return False
            self._keys.add(key)
            heapq.heappush(self._heap, (priority, next(self._counter), key, item))
            return True

    def dequeue(self):
        """(key, item) or None. The key stays claimed until done(key)."""
        with self._lock:
            if not self._heap:
                return None
            _, _, key, item = heapq.heappop(self._heap)
            return key, item

    def done(self, key) -> None:
        """Release the key so it can be re-enqueued (e.g. retry after
        backoff)."""
        with self._lock:
            self._keys.discard(key)

    def in_flight(self) -> int:
        """Keys claimed by a dequeue() but not yet released via done() —
        ops some drain thread is executing right now. Shutdown waits on
        this before concluding a flush pass made no progress."""
        with self._lock:
            return len(self._keys) - len(self._heap)

    def __len__(self) -> int:
        with self._lock:
            return len(self._heap)
