"""Dogfood pipeline gate: self-traces become first-class ingested data.

`selftrace_ingest_enabled` (a `self_tracing:` key, default off) closes
the "tempo traces tempo" loop: tracing.InProcessExporter pushes every
finished self-trace span through the normal distributor/TenantInstance
ingest path into the reserved ``_selftrace`` tenant, and THIS module
enriches those traces where the plain exporter cannot see:

  - ``annotate_query``: a finished request-scope QueryStats breakdown
    attaches as ``query.*`` attributes on the request span, so the
    trace of a slow search carries its own cost accounting.

The per-stage ``dispatch.<stage>`` children (build/h2d/compile/execute/
d2h/lock_wait, with transfer bytes and the jit-cache verdict) come from
the dispatch profiler itself, with the intervals its stage timers
observed (observability/profile.py), whenever a tracer records — so
structural queries like ``{ span.stage = "h2d" && duration > 50ms }``
over this tenant mean what they say.

Noop contract (the PR 9 stance, statically checked by the
NoopContractChecker): with the gate off every call site pays ONE
attribute read — no allocation, no clock, no lock — and outputs are
byte-identical. Feedback safety: the ingest-of-self-spans path runs
under tracing._suppressed, so the spans describing the self-ingest are
never themselves traced; additionally the hook bails when the current
span is not recording, which covers suppressed and sampled-out paths.

The anomaly flight recorder (observability/flightrecorder.RECORDER)
shares this gate: breaker trips, watchdog fires and slow queries
snapshot bounded diagnostic bundles whose trace ids resolve in
``_selftrace``.
"""

from __future__ import annotations

from . import tracing


class SelfTraceGate:
    """Process-wide gate (module singleton ``SELFTRACE``, the PROFILER
    idiom): tracing.init_tracing flips ``ingest_enabled`` from the
    ``self_tracing:`` config block; hot call sites read the one
    attribute and branch out when the dogfood loop is off."""

    def __init__(self) -> None:
        self.ingest_enabled = False

    def annotate_query(self, d: dict) -> None:
        """Attach a finished request-scope QueryStats dict (to_dict
        form) as flat ``query.*`` attributes on the current span — the
        request-scope span when called from the registry's publish on
        the request thread. Scalars only: nested breakdowns stay in the
        explain payload; the span carries the headline costs a trace
        reader triages by."""
        if not self.ingest_enabled:
            return
        span = tracing.current_span()
        if not span.recording:
            return
        span.set_attribute("query.wall_ms", d.get("wall_ms", 0.0))
        span.set_attribute("query.device_seconds",
                           d.get("device_seconds", 0.0))
        span.set_attribute("query.blocks_inspected",
                           d.get("blocks_inspected", 0))
        b = d.get("bytes_inspected") or {}
        span.set_attribute("query.bytes_host", b.get("host", 0))
        span.set_attribute("query.bytes_device", b.get("device", 0))
        span.set_attribute("query.dispatches", d.get("dispatches", 0))
        if d.get("fused_dispatches"):
            span.set_attribute("query.fused_dispatches",
                               d["fused_dispatches"])
        if d.get("subqueries"):
            span.set_attribute("query.subqueries", d["subqueries"])


SELFTRACE = SelfTraceGate()


def configure(ingest_enabled: bool | None = None,
              flight_recorder_max: int | None = None) -> SelfTraceGate:
    """Apply the self_tracing config block to the process gate AND the
    flight recorder (one gate, two surfaces — the recorder's triggers
    are only meaningful while the triggering trace is queryable)."""
    from . import flightrecorder

    if ingest_enabled is not None:
        SELFTRACE.ingest_enabled = bool(ingest_enabled)
        flightrecorder.RECORDER.enabled = bool(ingest_enabled)
    if flight_recorder_max is not None:
        flightrecorder.RECORDER.resize(int(flight_recorder_max))
    return SELFTRACE
