"""Request hedging for tail-latency control.

Role-equivalent to the reference's cristalhq/hedgedhttp usage (querier
external endpoints querier.go:103-109, backend instrumentation
hedged_requests.go): launch the call; if it hasn't answered within
`hedge_after_s`, launch up to `max_hedges` duplicates and take the first
result. Wasted duplicates are abandoned (their threads finish and are
discarded).
"""

from __future__ import annotations

import queue as _queue
import threading


def hedged_call(fn, *args, hedge_after_s: float = 0.5, max_hedges: int = 2):
    """Run fn(*args), hedging duplicates after a delay; first completion
    (result or raise) wins. max_hedges counts EXTRA attempts.

    Each attempt gets its own daemon thread (no shared pool: a pool's
    workers block on slow endpoints and then hedge submissions queue
    behind the very calls they were meant to race — starvation exactly
    when hedging matters). Losing attempts run to completion and are
    discarded."""
    results: _queue.Queue = _queue.Queue()

    def attempt():
        try:
            results.put((True, fn(*args)))
        except Exception as e:  # noqa: BLE001 — re-raised in the caller
            results.put((False, e))

    total = 1 + max_hedges
    launched = 1
    failures = 0
    threading.Thread(target=attempt, daemon=True).start()
    while True:
        try:
            ok, val = results.get(
                timeout=hedge_after_s if launched < total else None
            )
        except _queue.Empty:
            threading.Thread(target=attempt, daemon=True).start()
            launched += 1
            continue
        if ok:
            return val
        failures += 1
        if failures >= launched:
            # every launched attempt failed — hedge once more if allowed,
            # otherwise surface the error
            if launched < total:
                threading.Thread(target=attempt, daemon=True).start()
                launched += 1
                continue
            raise val
        # other attempts still in flight: keep waiting for one to succeed


class HedgedBackend:
    """RawBackend wrapper hedging read/read_range (object-store tail
    latency is the reason hedging exists)."""

    def __init__(self, inner, hedge_after_s: float = 0.5, max_hedges: int = 2):
        self.inner = inner
        self.hedge_after_s = hedge_after_s
        self.max_hedges = max_hedges

    def read(self, tenant, block_id, name):
        return hedged_call(self.inner.read, tenant, block_id, name,
                           hedge_after_s=self.hedge_after_s,
                           max_hedges=self.max_hedges)

    def read_range(self, tenant, block_id, name, offset, length):
        return hedged_call(self.inner.read_range, tenant, block_id, name,
                           offset, length, hedge_after_s=self.hedge_after_s,
                           max_hedges=self.max_hedges)

    def __getattr__(self, name):
        return getattr(self.inner, name)
