"""Deadline-bounded device dispatch: the watchdog around every kernel.

A device that stops answering hangs the CALLING thread at the dispatch
(or its H2D/D2H transfer) with no way to interrupt it from Python. The
guard therefore runs the dispatch body on a watchdog worker thread and
bounds the WAIT: past ``search_device_dispatch_timeout_s`` (clamped to the
request deadline's remaining budget) the caller abandons the worker,
books a breaker fault with the dispatch's profiler mode as stage
context, and raises :class:`DeviceDispatchTimeout` — which the batcher
catches and answers through the byte-identical host path. A backend
error from the dispatch (XLA runtime / injected) books the same way as
kind=error, with the exception's text handed to the breaker, which
logs it: the absorbed answer is correct, so the log line is the only
place the cause can be read.

The abandoned worker thread finishes (or never does) on its own; the
pool bounds how many can leak — and after ``threshold`` faults the
breaker is open, so nothing new is submitted at a wedged device anyway.

Noop contract: with the breaker disabled and no faultpoint armed,
``run`` is two attribute reads and a direct call — no thread handoff,
no clock, byte-identical results (bench phase ``chaos`` asserts <2%
dispatch overhead). With the guard active but the watchdog disabled
(``timeout_s <= 0`` and no request deadline) the body runs inline too:
faults are still classified, only the hang-bounding needs the thread.

Thread-local plumbing: profiler records finish on the thread that runs
the dispatch, and query-stats attribution collects them via a
THREAD-LOCAL collector stack (observability/profile.collect_records).
The guard propagates the submitter's open collector stack into the
worker so a guarded dispatch attributes exactly like an inline one.
"""

from __future__ import annotations

import concurrent.futures
import contextvars
import threading
from typing import Callable, TypeVar

from tempo_tpu.observability.flightrecorder import (RECORDER,
                                                    TRIGGER_WATCHDOG)

from . import deadline as _deadline
from .breaker import BREAKER
from .faults import FAULTS, InjectedFault


class DeviceFault(Exception):
    """A device dispatch failed in a way the host path can absorb."""


class DeviceDispatchTimeout(DeviceFault):
    """The watchdog deadline elapsed with the dispatch still running."""


class DeviceDispatchError(DeviceFault):
    """The dispatch raised a backend/runtime (or injected) error."""


class DispatchLockTimeout(DeviceFault):
    """The collective dispatch-lock wait exceeded its bound — some other
    dispatch is wedged while holding it (the PR 1 rendezvous-deadlock
    class, detectable at runtime instead of merely avoided)."""


T = TypeVar("T")


def _is_device_error(e: BaseException) -> bool:
    """Errors the host path can absorb: injected faults, jax/XLA
    runtime errors, bare RuntimeErrors from the backend. Anything else
    (ValueError from a shape bug, a real KeyError) is a BUG and must
    propagate un-wrapped — silently host-retrying it would mask it."""
    if isinstance(e, InjectedFault):
        return True
    mod = type(e).__module__ or ""
    if mod.startswith(("jax", "jaxlib")):
        return True
    return isinstance(e, RuntimeError)


class DispatchGuard:
    """Process-wide dispatch watchdog (module singleton ``GUARD``, the
    PROFILER idiom). ``run(mode, fn)`` executes one device dispatch
    body; ``mode`` is the profiler's dispatch mode (batched | coalesced |
    mesh | dict_probe | h2d | d2h) and becomes the fault's
    stage context."""

    # bounds leaked hung workers between breaker trips; the breaker
    # opens after `threshold` faults, so steady-state leakage is zero
    _MAX_WORKERS = 32

    def __init__(self) -> None:
        self.timeout_s = 30.0       # search_device_dispatch_timeout_s
        self.lock_timeout_s = 60.0  # search_dispatch_lock_timeout_s
        self._pool: concurrent.futures.ThreadPoolExecutor | None = None
        self._pool_lock = threading.Lock()

    @property
    def active(self) -> bool:
        """Whether dispatches route through the guard at all — the one
        condition of the noop contract: breaker off + faults disarmed
        means every dispatch site runs exactly the historical inline
        code after two attribute reads."""
        return BREAKER.enabled or FAULTS.active

    def _ensure_pool(self) -> concurrent.futures.ThreadPoolExecutor:
        pool = self._pool
        if pool is None:
            with self._pool_lock:
                pool = self._pool
                if pool is None:
                    pool = self._pool = \
                        concurrent.futures.ThreadPoolExecutor(
                            max_workers=self._MAX_WORKERS,
                            thread_name_prefix="device-dispatch")
        return pool

    def run(self, mode: str, fn: Callable[[], T]) -> T:
        """Execute one device dispatch body under the watchdog. Returns
        fn()'s result; raises DeviceFault (timeout / classified backend
        error, breaker fault booked) or DeadlineExceeded (the request's
        budget ran out before the dispatch could start)."""
        if not (BREAKER.enabled or FAULTS.active):
            return fn()
        from tempo_tpu.observability import profile

        timeout = self.timeout_s if self.timeout_s > 0 else None
        dl = _deadline.current()
        if dl is not None:
            rem = dl.remaining()
            if rem <= 0:
                raise _deadline.DeadlineExceeded(
                    f"request deadline expired before {mode} dispatch")
            timeout = rem if timeout is None else min(timeout, rem)

        if timeout is None:
            # no watchdog wanted: inline, but still inject + classify
            try:
                if FAULTS.active:
                    FAULTS.hit("device_dispatch_raise")
                    FAULTS.hit("device_dispatch_hang")
                out = fn()
            except DeviceFault:
                raise  # already booked at its source (lock timeout)
            except _deadline.DeadlineExceeded:
                raise
            except Exception as e:
                if _is_device_error(e):
                    BREAKER.record_fault(
                        "error", mode=mode,
                        detail=f"{type(e).__name__}: {e}")
                    raise DeviceDispatchError(
                        f"{mode}: {type(e).__name__}: {e}") from e
                raise
            BREAKER.record_success(mode=mode)
            return out

        # the submitter's open profiler-record collectors (thread-local)
        # follow the dispatch onto the worker thread — see module doc
        stack = getattr(profile._collect_local, "stack", None)
        ctx = contextvars.copy_context()

        def worker() -> T:
            if stack is not None:
                profile._collect_local.stack = stack
            try:
                if FAULTS.active:
                    FAULTS.hit("device_dispatch_raise")
                    FAULTS.hit("device_dispatch_hang")
                return ctx.run(fn)
            finally:
                if stack is not None:
                    profile._collect_local.stack = None

        fut = self._ensure_pool().submit(worker)
        try:
            out = fut.result(timeout=timeout)
        except concurrent.futures.TimeoutError:
            fut.cancel()  # no-op if running; the worker is abandoned
            BREAKER.record_fault(
                "timeout", mode=mode,
                detail=f"no result within the {timeout:.3f}s watchdog "
                       "deadline")
            # flight recorder: a watchdog fire means a dispatch is
            # wedged RIGHT NOW — snapshot before the abandonment
            # propagates (no lock held here)
            if RECORDER.enabled:
                RECORDER.record(TRIGGER_WATCHDOG,
                                detail={"mode": mode,
                                        "timeout_s": round(timeout, 3)})
            raise DeviceDispatchTimeout(
                f"device dispatch ({mode}) exceeded its "
                f"{timeout:.3f}s watchdog deadline") from None
        except DeviceFault:
            raise  # booked at its source (e.g. dispatch-lock timeout)
        except _deadline.DeadlineExceeded:
            raise
        except Exception as e:
            if _is_device_error(e):
                BREAKER.record_fault(
                    "error", mode=mode, detail=f"{type(e).__name__}: {e}")
                raise DeviceDispatchError(
                    f"{mode}: {type(e).__name__}: {e}") from e
            raise
        BREAKER.record_success(mode=mode)
        return out


GUARD = DispatchGuard()
