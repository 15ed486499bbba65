"""Device circuit breaker: closed → open → half-open → closed.

One hung dispatch is a fault; N faults inside a window mean the device
itself has stopped answering, and every further dispatch would burn a
watchdog timeout learning the same thing. The breaker aggregates the
faults the dispatch guard books and flips the whole serving path to the
host route in one place:

  closed     normal: every dispatch allowed; faults accumulate in the
             sliding window; threshold trips to open.
  open       device blocked: ``allow_device()`` is False so the batcher
             host-routes groups, ``planner.stage_veto`` /
             ``pipeline._use_device_probe`` keep dictionaries on the
             host path, and staging uploads stop. After ``cooldown_s``
             the next ``allow_device()`` transitions to half-open.
  half-open  recovery probing: a LIMITED number of dispatches (probe
             tokens) are allowed through the real device path. One
             success closes the breaker (and clears the window); one
             fault re-opens it and restarts the cooldown.

Transitions emit ``tempo_search_device_breaker_transitions_total``,
update the state gauge, annotate the active self-trace span, and log —
``/status``'s device block reads :meth:`snapshot` instead of ad-hoc
probing. Every booked fault is also
logged at error level with the absorbed exception's text (rate-limited
per kind and mode): the host route answers byte-identically, so that
line and ``last_fault`` are where a compiler refusal or an HBM
RESOURCE_EXHAUSTED can be read.

Hot-path contract: with the breaker disabled (or closed),
``allow_device`` / ``record_success`` are attribute reads — no lock, no
clock. Only faults and non-closed states pay for bookkeeping.
"""

from __future__ import annotations

import threading
import time
from collections import deque

from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import tracing
from tempo_tpu.observability.flightrecorder import (RECORDER,
                                                    TRIGGER_BREAKER)
from tempo_tpu.observability.log import TenantTokenBucket, get_logger

log = get_logger("tempo_tpu.breaker")
_DETAIL_MAX = 4000  # chars of an absorbed exception's text kept

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half_open"

_STATE_CODE = {CLOSED: 0, HALF_OPEN: 1, OPEN: 2}


class CircuitBreaker:
    def __init__(self, threshold: int = 3, window_s: float = 30.0,
                 cooldown_s: float = 5.0, half_open_probes: int = 1,
                 enabled: bool = False):
        self.enabled = enabled
        self.threshold = threshold
        self.window_s = window_s
        self.cooldown_s = cooldown_s
        self.half_open_probes = half_open_probes
        self._state = CLOSED
        self._faults: deque[float] = deque()   # monotonic fault times
        self._opened_t: float | None = None
        self._probe_tokens = 0
        self._probe_granted_t = 0.0
        self._transitions: dict[str, int] = {}
        self._last_fault: dict[str, object] | None = None
        self._last_fault_t: float | None = None
        # fault log lines are rate-limited per kind/mode: a hung device
        # repeats the same fault per dispatch, and the log must not
        # become the incident
        self._fault_log = TenantTokenBucket(rate=1.0, burst=5)
        self._lock = threading.Lock()

    # ---- hot-path reads ----

    @property
    def state(self) -> str:
        return self._state

    def allow_device(self) -> bool:
        """May the caller start a NEW device dispatch/staging? Closed
        (or disabled) answers True from attribute reads alone. Open
        answers False until the cooldown elapses, then flips to
        half-open and hands out probe tokens; half-open answers True
        only while a probe token is available, so recovery probing never
        stampedes a device that just came back."""
        if not self.enabled or self._state == CLOSED:
            return True
        with self._lock:
            now = time.monotonic()
            if self._state == OPEN:
                if (self._opened_t is not None
                        and now - self._opened_t >= self.cooldown_s):
                    self._transition(HALF_OPEN)
                    self._probe_tokens = self.half_open_probes
                    self._probe_granted_t = now
                else:
                    return False
            if self._state == HALF_OPEN:
                if self._probe_tokens > 0:
                    self._probe_tokens -= 1
                    self._probe_granted_t = now
                    return True
                if now - self._probe_granted_t >= self.cooldown_s:
                    # every granted probe went silent — its group pruned
                    # away, its request early-quit or deadlined before
                    # dispatching — so neither success nor fault ever
                    # reported back. Re-grant after a cooldown rather
                    # than wedging in half-open forever.
                    self._probe_granted_t = now
                    return True
                return False
            return self._state == CLOSED  # raced a concurrent close

    def blocking(self) -> bool:
        """True while the breaker diverts work off the device — the
        stage-veto / probe-placement gate. Half-open still blocks
        STAGING decisions (only allow_device's counted probes run on
        device) so a recovering chip isn't immediately handed a 720MB
        dictionary upload."""
        return self.enabled and self._state != CLOSED

    # ---- event booking (dispatch guard + lock timeout call these) ----

    def record_fault(self, kind: str, mode: str = "",
                     detail: str = "") -> None:
        """Book one device fault (kind=timeout|error|lock_timeout,
        mode = the profiler's dispatch mode for stage context, detail =
        the absorbed exception's text). Counted and logged even when
        the breaker is disabled — the operator still sees the faults;
        only the state machine is gated."""
        obs.device_faults.inc(kind=kind, mode=mode or "unknown")
        detail = detail[:_DETAIL_MAX]
        if self._fault_log.allow(f"{kind}/{mode}"):
            log.error("device fault absorbed by the host route: "
                      "kind=%s mode=%s %s", kind, mode or "unknown", detail)
        span = tracing.current_span()
        if span.recording:
            span.add_event("device.fault", kind=kind, mode=mode)
        if not self.enabled:
            return
        now = time.monotonic()
        tripped = False
        with self._lock:
            self._last_fault = {"kind": kind, "mode": mode,
                                "detail": detail}
            self._last_fault_t = now
            if self._state == HALF_OPEN:
                # the recovery probe failed: straight back to open,
                # cooldown restarts
                self._transition(OPEN)
                self._opened_t = now
                self._probe_tokens = 0
                tripped = True
            else:
                self._faults.append(now)
                while (self._faults
                       and now - self._faults[0] > self.window_s):
                    self._faults.popleft()
                if (self._state == CLOSED
                        and len(self._faults) >= self.threshold):
                    self._transition(OPEN)
                    self._opened_t = now
                    tripped = True
        # the flight-recorder snapshot happens OUTSIDE the breaker lock
        # (it re-reads BREAKER.snapshot among others — the recorder's
        # lock must stay a leaf in the process lock graph)
        if tripped and RECORDER.enabled:
            RECORDER.record(TRIGGER_BREAKER,
                            detail={"kind": kind, "mode": mode})

    def record_success(self, mode: str = "") -> None:
        """Book one successful device dispatch. Closed state returns on
        attribute reads (the per-dispatch steady-state cost); a success
        in half-open closes the breaker and clears the fault window."""
        if not self.enabled or self._state == CLOSED:
            return
        with self._lock:
            if self._state == HALF_OPEN:
                self._transition(CLOSED)
                self._faults.clear()
                self._opened_t = None
                self._probe_tokens = 0

    def reset(self) -> None:
        """Test/bench hook: back to closed with an empty window."""
        with self._lock:
            if self._state != CLOSED:
                self._transition(CLOSED)
            self._faults.clear()
            self._opened_t = None
            self._probe_tokens = 0
            self._probe_granted_t = 0.0
            self._last_fault = None
            self._last_fault_t = None
            self._fault_log = TenantTokenBucket(rate=1.0, burst=5)

    # ---- internals ----

    def _transition(self, to: str) -> None:
        """Caller holds self._lock."""
        frm = self._state
        if frm == to:
            return
        self._state = to
        self._transitions[f"{frm}->{to}"] = \
            self._transitions.get(f"{frm}->{to}", 0) + 1
        obs.breaker_transitions.inc(**{"from": frm, "to": to})
        obs.breaker_state.set(_STATE_CODE[to])
        span = tracing.current_span()
        if span.recording:
            span.add_event("breaker.transition", **{"from": frm, "to": to})
        log.warning("device circuit breaker: %s -> %s "
                    "(faults_in_window=%d threshold=%d)",
                    frm, to, len(self._faults), self.threshold)

    # ---- operator surface ----

    def snapshot(self) -> dict[str, object]:
        """The /status device-block + /debug/faults breaker view."""
        with self._lock:
            now = time.monotonic()
            last: dict[str, object] | None = None
            if self._last_fault is not None \
                    and self._last_fault_t is not None:
                last = dict(self._last_fault)
                last["age_s"] = round(now - self._last_fault_t, 3)
            return {
                "enabled": self.enabled,
                "state": self._state,
                "faults_in_window": sum(
                    1 for t in self._faults if now - t <= self.window_s),
                "threshold": self.threshold,
                "window_s": self.window_s,
                "cooldown_s": self.cooldown_s,
                "open_age_s": (round(now - self._opened_t, 3)
                               if self._opened_t is not None
                               and self._state != CLOSED else None),
                "transitions": dict(self._transitions),
                "last_fault": last,
            }


BREAKER = CircuitBreaker()
