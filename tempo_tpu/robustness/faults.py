"""Fault-injection harness: named faultpoints, true noop when disarmed.

The breaker/watchdog/fallback machinery is only trustworthy if tier-1
can PROVE it — which needs deterministic, targeted failures. This module
is the process-wide registry of named faultpoints: each is a site in the
real code (``FAULTS.hit("device_dispatch_hang")``) that, when ARMED,
injects a delay and/or raises :class:`InjectedFault`; when disarmed it
costs nothing (call sites branch out on ONE attribute read,
``FAULTS.active`` — the PROFILER/TELEMETRY idiom; ``hit`` itself is
never reached).

Arming:
  - test fixture / code: ``FAULTS.arm("flush_error", count=2)`` or the
    ``with FAULTS.armed("device_dispatch_hang", delay_s=5):`` context
  - config: ``storage.robustness_faults: "poll_error:count=1"``
  - env: ``TEMPO_FAULTS="device_dispatch_raise:p=0.5;h2d_delay:delay=0.2"``

Spec grammar: ``name[:k=v[,k=v...]][;name...]`` with keys ``p``
(probability, default 1), ``count`` (fires before auto-disarm, default
unlimited), ``delay`` (seconds slept on fire, default 0), ``raise``
(0/1; default from the catalog — *_raise/*_error faultpoints raise,
*_hang/*_delay ones sleep).

Every faultpoint must be registered in :data:`CATALOG` (description +
wired site) — ``tests/test_faults.py`` asserts the catalog matches
``docs/robustness.md``, the config-docs drift pattern. ``/debug/faults``
renders the live arming state.
"""

from __future__ import annotations

import contextlib
import random
import threading
import time
from typing import Any, Iterator


class InjectedFault(Exception):
    """The error an armed *_raise/*_error faultpoint throws. A plain
    Exception (not a DeviceFault): non-device sites (backend read,
    flush, poll) must surface it exactly like the IO error it stands in
    for; the dispatch guard classifies it as a device fault only at
    device sites."""


# name -> (description, wired-at). The single source of truth the docs
# drift test checks docs/robustness.md against.
CATALOG: dict[str, tuple[str, str]] = {
    "device_dispatch_raise": (
        "raise from inside the watchdogged device dispatch (backend "
        "error path: breaker fault kind=error, host fallback)",
        "robustness/dispatch.py DispatchGuard.run worker"),
    "device_dispatch_hang": (
        "sleep inside the watchdogged device dispatch (hung-device "
        "path: watchdog timeout, breaker fault kind=timeout, host "
        "fallback); arm with delay= past the watchdog deadline",
        "robustness/dispatch.py DispatchGuard.run worker"),
    "h2d_delay": (
        "sleep inside the host->device staging put (slow or hung H2D; "
        "with delay past the watchdog deadline the staging dispatch "
        "times out and the group host-routes)",
        "search/multiblock.py place_batch"),
    "dispatch_lock_hang": (
        "sleep while HOLDING the process-wide collective dispatch lock "
        "— makes every other mesh dispatch wait, driving "
        "dispatch-lock timeouts (the PR 1 rendezvous-deadlock class, "
        "now detectable at runtime)",
        "parallel/mesh.py locked_collective"),
    "backend_read_error": (
        "raise from an object-store read (replica/backend flake: the "
        "querier books a partial result instead of failing the query)",
        "backend/local.py + backend/mock.py read"),
    "flush_error": (
        "raise from the ingester's block completion (flush retries + "
        "backoff path; the freshness gauges age instead of lying)",
        "modules/ingester.py TenantInstance.complete_one"),
    "poll_error": (
        "raise from the blocklist poll (a reader that stops seeing new "
        "blocks; the canary and freshness gauges surface it)",
        "db/tempodb.py TempoDB.poll"),
    "replica_error": (
        "raise from an ingester-replica search fan-out leg (partial "
        "results counter reason=replica, SearchMetrics.partial set)",
        "modules/querier.py Querier.search_recent"),
}

# names whose default effect is to RAISE when armed without raise=/delay=
_RAISE_DEFAULT = tuple(
    n for n in CATALOG if n.endswith(("_raise", "_error")))


class _Faultpoint:
    __slots__ = ("name", "probability", "count", "delay_s", "raises",
                 "fired")

    def __init__(self, name: str, probability: float = 1.0,
                 count: int | None = None, delay_s: float = 0.0,
                 raises: bool | None = None):
        self.name = name
        self.probability = float(probability)
        self.count = None if count is None else int(count)
        self.delay_s = float(delay_s)
        self.raises = (name in _RAISE_DEFAULT if raises is None
                       else bool(raises))
        self.fired = 0

    def as_dict(self) -> dict[str, object]:
        return {
            "probability": self.probability,
            "count": self.count,
            "delay_s": self.delay_s,
            "raises": self.raises,
            "fired": self.fired,
        }


class FaultRegistry:
    """Process-wide armed-faultpoint set. ``active`` is the one-word
    fast path every call site reads; it is True only while at least one
    faultpoint is armed, so the disarmed steady state never takes the
    lock or even calls ``hit``."""

    def __init__(self) -> None:
        self.active = False
        self._armed: dict[str, _Faultpoint] = {}
        self._fired_total: dict[str, int] = {}
        self._lock = threading.Lock()
        self._rng = random.Random(0x7e3)  # deterministic under seeding

    # ---- arming ----

    def arm(self, name: str, probability: float = 1.0,
            count: int | None = None, delay_s: float = 0.0,
            raises: bool | None = None) -> None:
        if name not in CATALOG:
            raise ValueError(
                f"unknown faultpoint {name!r}; registered: "
                f"{sorted(CATALOG)}")
        with self._lock:
            self._armed[name] = _Faultpoint(
                name, probability=probability, count=count,
                delay_s=delay_s, raises=raises)
            self.active = True

    def arm_spec(self, spec: str) -> None:
        """Arm from the config/env grammar (module docstring)."""
        for part in spec.split(";"):
            part = part.strip()
            if not part:
                continue
            name, _, args = part.partition(":")
            kw: dict[str, Any] = {}
            for kv in args.split(","):
                kv = kv.strip()
                if not kv:
                    continue
                k, _, v = kv.partition("=")
                k = k.strip()
                if k in ("p", "probability"):
                    kw["probability"] = float(v)
                elif k == "count":
                    kw["count"] = int(v)
                elif k in ("delay", "delay_s"):
                    kw["delay_s"] = float(v)
                elif k in ("raise", "raises"):
                    kw["raises"] = v.strip() not in ("0", "false", "")
                else:
                    raise ValueError(
                        f"unknown faultpoint param {k!r} in {part!r}")
            self.arm(name.strip(), **kw)

    def disarm(self, name: str) -> None:
        with self._lock:
            self._armed.pop(name, None)
            self.active = bool(self._armed)

    def disarm_all(self) -> None:
        with self._lock:
            self._armed.clear()
            self.active = False

    def seed(self, seed: int) -> None:
        """Re-seed the probability rolls (deterministic chaos tests)."""
        with self._lock:
            self._rng = random.Random(seed)

    @contextlib.contextmanager
    def armed(self, name: str, **kw: Any) -> Iterator["FaultRegistry"]:
        """Test-fixture arming: disarms on exit even on failure."""
        self.arm(name, **kw)
        try:
            yield self
        finally:
            self.disarm(name)

    # ---- the injection site ----

    def hit(self, name: str) -> None:
        """Fire faultpoint `name` if armed: sleep its delay, then raise
        if it is a raising point. Call sites guard with ``if
        FAULTS.active:`` so this is never reached while disarmed."""
        with self._lock:
            fp = self._armed.get(name)
            if fp is None:
                return
            if fp.probability < 1.0 and self._rng.random() >= fp.probability:
                return
            fp.fired += 1
            self._fired_total[name] = self._fired_total.get(name, 0) + 1
            if fp.count is not None and fp.fired >= fp.count:
                del self._armed[name]
                self.active = bool(self._armed)
            delay, raises = fp.delay_s, fp.raises
        from tempo_tpu.observability import metrics as obs

        obs.faults_injected.inc(faultpoint=name)
        if delay > 0:
            time.sleep(delay)
        if raises:
            raise InjectedFault(f"injected fault: {name}")

    # ---- operator surface ----

    def snapshot(self) -> dict[str, object]:
        """/debug/faults payload: catalog + live arming state."""
        with self._lock:
            armed = {n: fp.as_dict() for n, fp in self._armed.items()}
            fired = dict(self._fired_total)
        return {
            "active": self.active,
            "armed": armed,
            "fired_total": fired,
            "catalog": {n: {"description": d, "site": s}
                        for n, (d, s) in sorted(CATALOG.items())},
        }


FAULTS = FaultRegistry()
