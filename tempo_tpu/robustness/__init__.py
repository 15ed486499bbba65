"""Robustness substrate: graceful degradation when the device dies.

A device that stops answering must not take the SERVING path down with
it: without this package a production query hitting a hung or erroring
device dispatch just hung with it. Because the engine keeps
byte-identical host paths for every scan and probe variant (the
dual-path premise of "To GPU or Not to GPU", arxiv 2605.15957), graceful
degradation is purely a CONTROL-PLANE problem, solved by three
cooperating pieces:

  deadline.py   request deadlines (contextvar, http → frontend →
                querier → TempoDB via the worker pool's context copy)
                so sharded sub-queries stop queueing behind a dead
                device instead of stacking.
  dispatch.py   a watchdog around every device dispatch
                (``search_device_dispatch_timeout_s``): a dispatch that
                exceeds it — or raises a backend error — is recorded as
                a device fault with its profiler stage context and
                surfaces as a catchable :class:`DeviceFault` instead of
                a hang.
  breaker.py    the device circuit breaker: N faults within a window
                trip it (closed → open → half-open with probe
                dispatches to recover); while it blocks,
                ``planner.stage_veto`` / ``pipeline._use_device_probe``
                / the batcher route everything through the existing
                host paths and ``/status``'s device block reads
                breaker state instead of ad-hoc probing.
  faults.py     the fault-injection harness proving all of the above in
                tier-1: named faultpoints armable by config/env/test
                fixture, compiled to a true noop when disarmed (the
                PROFILER idiom), exposed at ``/debug/faults``.

Noop contract: breaker off + faults disarmed costs one attribute read
per dispatch site and results are byte-identical (bench phase ``chaos``
asserts both, the PR 5/7/8 pattern). Imports here stay LEAF-LEVEL
(stdlib + observability only) so search/parallel/db can all depend on
this package without cycles.
"""

from __future__ import annotations

from .breaker import BREAKER, CircuitBreaker
from .deadline import Deadline, DeadlineExceeded
from .dispatch import (
    GUARD,
    DeviceDispatchError,
    DeviceDispatchTimeout,
    DeviceFault,
    DispatchLockTimeout,
)
from .faults import FAULTS, InjectedFault
from . import deadline

__all__ = [
    "BREAKER", "CircuitBreaker", "Deadline", "DeadlineExceeded",
    "DeviceDispatchError", "DeviceDispatchTimeout", "DeviceFault",
    "DispatchLockTimeout", "FAULTS", "GUARD", "InjectedFault",
    "configure", "deadline",
]


def configure(breaker_enabled: bool | None = None,
              fault_threshold: int | None = None,
              window_s: float | None = None,
              cooldown_s: float | None = None,
              dispatch_timeout_s: float | None = None,
              lock_timeout_s: float | None = None,
              faults_spec: str | None = None) -> None:
    """Apply config (TempoDBConfig.search_breaker_* /
    search_device_dispatch_timeout_s / search_dispatch_lock_timeout_s /
    robustness_faults) to the process-wide breaker, dispatch guard and
    fault registry — the most recent TempoDB wins, matching how the
    profiler/planner/query-stats configure. The ``TEMPO_FAULTS`` env
    spec is applied in ADDITION to the config spec so a triage session
    can arm a faultpoint without a config rollout."""
    import os

    if fault_threshold is not None:
        BREAKER.threshold = max(1, int(fault_threshold))
    if window_s is not None:
        BREAKER.window_s = float(window_s)
    if cooldown_s is not None:
        BREAKER.cooldown_s = float(cooldown_s)
    if breaker_enabled is not None:
        BREAKER.enabled = bool(breaker_enabled)
    if dispatch_timeout_s is not None:
        GUARD.timeout_s = float(dispatch_timeout_s)
    if lock_timeout_s is not None:
        GUARD.lock_timeout_s = float(lock_timeout_s)
    if faults_spec is not None:
        if faults_spec:
            FAULTS.arm_spec(faults_spec)
        env = os.environ.get("TEMPO_FAULTS", "")
        if env:
            FAULTS.arm_spec(env)
