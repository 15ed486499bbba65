"""Hot-tier live search: rolling device batches over in-flight traces.

The reference era only sees a trace after flush + poll (PAPER.md:
FlatBuffer-search era) — measured push→searchable is flush+poll bound
at p50 2.6s / p99 3.4s. This module closes the gap: the ingesters'
LIVE (not-yet-cut) traces absorb into a per-tenant rolling columnar
stage scanned by the SAME fused scan kernel as backend blocks, and the
WAL head/completing generations scan through the identical machinery
via :func:`scan_search_data` (the `StreamingSearchBlock` gate-on path).
The per-entry Python `search_data_matches` walk becomes the gate-off
fallback route.

Staging is epoch-versioned micro-batching: every absorb/evict bumps the
tenant epoch; a search rebuilds the columnar container only when the
epoch moved, and the container's page axis pads to a fixed pow2 `tier`
capacity so the jit key stays SHAPE-ONLY — absorbing entries within a
tier re-enters the same compiled kernel with a new traced live count;
only a tier overflow (capacity doubling) pays a fresh XLA trace.

Eviction follows the ingester lifecycle: a cut trace leaves the live
stage for the WAL head (scanned there), a completed block leaves the
WAL for the ingester's recently-flushed list, and the recently-flushed
leg retires EARLY once the backend block is poll-visible
(`mark_poll_visible`, fed by TempoDB.poll) so the reader leg and the
ingester leg never double-answer; the 300s recently-flushed window
remains the cross-process bound.

On top of the tier rides the tail-subscription API ("push me spans
matching P as they arrive"): standing queries registered per tenant,
evaluated against each push micro-batch, bounded queues with drop-oldest
overflow, per-tenant subscription caps.

`search_live_tier_enabled` false (default) is a TRUE noop: every hook
reads one attribute and returns; search takes the existing per-entry
walk byte-identically (asserted by tests/test_live_tier.py and the
analysis noop contracts).
"""

from __future__ import annotations

import functools
import threading
from collections import deque

import jax
import jax.numpy as jnp

from .data import (
    SearchData,
    clone_search_data,
    decode_search_data,
    search_data_matches,
)
from .dict_probe import _pow2
from .engine import DEFAULT_TOP_K, cpu_pinned, fetch_scan_out, resolve_top_k
from .multiblock import (
    MultiBlockEngine,
    batch_scan_kernel,
    compile_multi,
    stack_host,
)


def _tier_valid(entry_valid, n_pages, tier):
    """Mask capacity pages beyond the tenant's live page count.

    `tier` is the hot stage's static pow2 page-capacity descriptor —
    part of the jit key (static_argnames), so absorbing entries within
    a tier re-runs the SAME compiled kernel with only the traced
    `n_pages` changing; a tier overflow recompiles once for the doubled
    capacity. None = container staged without capacity semantics
    (passthrough, the legacy full-page layout).
    """
    if tier is None:
        return entry_valid
    page_live = (jnp.arange(entry_valid.shape[0], dtype=jnp.int32)[:, None]
                 < n_pages)
    return jnp.logical_and(entry_valid, page_live)


@functools.partial(jax.jit, static_argnames=("n_terms", "top_k", "widths",
                                             "plan", "tier"))
def hot_scan_kernel(cat, n_pages, term_keys, val_ranges, dur_lo, dur_hi,
                    win_start, win_end, span_cols=None, s_tables=None,
                    *, n_terms, top_k, widths=None, plan=None, tier=None):
    """The hot-tier dispatch: batch_scan_kernel over a capacity-padded
    rolling stage, a one-block batch (`cat`: stack_host's arrays).
    Delegation keeps it byte-identical to the backend-block scan — same
    match mask, same masked top-k — with one prelude: the static `tier`
    capacity descriptor masks pages beyond the traced live count so a
    stage scanned mid-absorb never reads a stale capacity page."""
    return batch_scan_kernel(
        cat["kv_key"], cat["kv_val"], cat["entry_start"], cat["entry_end"],
        cat["entry_dur"], _tier_valid(cat["entry_valid"], n_pages, tier),
        cat["page_block"], term_keys, val_ranges, None, dur_lo, dur_hi,
        win_start, win_end, None, None, cat.get("entry_dur_res"),
        span_cols, s_tables, n_terms=n_terms, top_k=top_k, widths=widths,
        plan=plan)


class _HotStage:
    """Epoch-cached columnar build over one entry set, stacked as a
    one-block batch. Rebuilds only when the epoch moved; the page axis
    pads to the pow2 `tier` so the kernel's jit key is shape-only (see
    module docstring)."""

    def __init__(self):
        self.epoch = -1
        self.host = None       # multiblock.HostBatch of the one block
        self.tier = 0

    def ensure(self, entries: list[SearchData], epoch: int):
        if self.epoch == epoch and self.host is not None:
            return self.host
        from .columnar import ColumnarPages

        pages = ColumnarPages.build(entries)
        self.tier = _pow2(pages.n_pages)
        self.host = stack_host([pages], pad_to=self.tier)
        self.epoch = epoch
        from tempo_tpu.observability import metrics as obs

        obs.live_tier_rebuilds.inc()
        return self.host


def scan_search_data(entries: list[SearchData], req, results,
                     stage: _HotStage, epoch: int) -> bool:
    """Kernel-scan a SearchData set — the replacement for the per-entry
    Python `search_data_matches` walk. Byte-identical to the
    backend-block host scan: same dictionary compile (may prune), same
    compiled structural plan (eval_host stays the gate-off route), same
    masked top-k and render path. Returns True when the scan handled
    the request (results updated; a dictionary prune counts — nothing
    could match), False when the caller must run the legacy walk."""
    from . import structural as _structural

    if not entries:
        return True
    host = stage.ensure(entries, epoch)
    mq = compile_multi(host.blocks, req, cache_on=host, host_only=True)
    if mq is None:  # dictionary prefilter pruned: no entry can match
        return True
    expr = _structural.structural_query(req)
    if expr is not None:
        mq.structural = _structural.compile_structural(
            expr, host.blocks, cache_on=host, host_only=True,
            entry_kv_slots=host.blocks[0].geometry.kv_per_entry)
    st = mq.structural
    with cpu_pinned():
        plan = s_tables = span_dev = None
        if st is not None:
            plan = st.plan
            s_tables = tuple(jnp.asarray(t) if t is not None else None
                             for t in st.tables())
            if host.span_cat is not None:
                span_dev = {k: jnp.asarray(v)
                            for k, v in host.span_cat.items()}
        out = hot_scan_kernel(
            {k: jnp.asarray(v) for k, v in host.cat.items()},
            jnp.int32(host.blocks[0].n_pages),
            jnp.asarray(mq.term_keys), jnp.asarray(mq.val_ranges),
            jnp.uint32(mq.dur_lo), jnp.uint32(min(mq.dur_hi, 0xFFFFFFFF)),
            jnp.uint32(mq.win_start),
            jnp.uint32(min(mq.win_end, 0xFFFFFFFF)),
            span_dev, s_tables,
            n_terms=mq.n_terms, top_k=resolve_top_k(DEFAULT_TOP_K, mq.limit),
            widths=host.widths, plan=plan, tier=stage.tier)
        _, inspected, scores, idx = fetch_scan_out(out)
    results.metrics.inspected_traces += inspected
    for m in MultiBlockEngine.results(host, mq, scores, idx):
        results.add(m)
    return True


class TailSubscription:
    """One standing query: a bounded notification queue with drop-oldest
    overflow (a slow consumer loses the OLDEST notifications and sees
    its `dropped` count rise, it never blocks the push path)."""

    def __init__(self, tenant: str, req, max_queue: int = 256):
        self.tenant = tenant
        self.req = req
        self.dropped = 0
        self.closed = False
        self._q: deque = deque()
        self._max_queue = max_queue
        self._cond = threading.Condition()

    def offer(self, meta) -> None:
        with self._cond:
            if self.closed:
                return
            if len(self._q) >= self._max_queue:
                self._q.popleft()
                self.dropped += 1
                from tempo_tpu.observability import metrics as obs

                obs.live_tail_dropped.inc(reason="queue",
                                          tenant=self.tenant)
            self._q.append(meta)
            self._cond.notify_all()

    def poll(self, timeout_s: float | None = None) -> list:
        """Drain pending notifications, blocking up to timeout_s for the
        first one. Returns [] on timeout or once closed."""
        with self._cond:
            if not self._q and not self.closed:
                self._cond.wait(timeout_s)
            out = list(self._q)
            self._q.clear()
            return out

    def close(self) -> None:
        with self._cond:
            self.closed = True
            self._cond.notify_all()


class _TenantHot:
    def __init__(self):
        self.entries: dict[bytes, SearchData] = {}  # live (uncut) traces
        self.epoch = 0
        self.stage = _HotStage()
        self.visible: set[str] = set()  # poll-visible backend block ids
        self.subs: list[TailSubscription] = []


class LiveTier:
    """Process-wide hot-tier gate + per-tenant rolling stages (the
    PACKING/STRUCTURAL/OWNERSHIP singleton idiom: the most recent
    TempoDB's config wins; `enabled=False` is a true noop — one
    attribute read per hook)."""

    def __init__(self):
        self.enabled = False
        self.max_entries = 4096
        self.max_subscriptions = 16
        self._lock = threading.Lock()
        self._tenants: dict[str, _TenantHot] = {}

    def configure(self, enabled: bool = False, max_entries: int = 4096,
                  max_subscriptions: int = 16) -> None:
        with self._lock:
            self.max_entries = int(max_entries)
            self.max_subscriptions = int(max_subscriptions)
            self._tenants = {}
            self.enabled = bool(enabled)

    def _tenant(self, tenant: str) -> _TenantHot:
        t = self._tenants.get(tenant)
        if t is None:
            t = self._tenants[tenant] = _TenantHot()
        return t

    # ---- ingest-side hooks (called with the instance lock held, so
    # tier state mirrors the ingester's live set deterministically; the
    # lock order instance.lock → tier lock is acyclic — LiveTier never
    # calls back into the ingester) ----

    def absorb(self, tenant: str, trace_id: bytes, raw: bytes) -> None:
        """Absorb one push micro-batch member into the live stage.
        Corrupt SearchData drops silently — exactly the lazy-decode
        behavior of `_LiveTrace.search_data`."""
        if not self.enabled:
            return
        if not raw:
            return
        try:
            sd = decode_search_data(raw, trace_id)
        except Exception:  # noqa: BLE001 — mirror the lazy-decode drop
            return
        with self._lock:
            t = self._tenant(tenant)
            prev = t.entries.get(trace_id)
            if prev is not None:
                merged = clone_search_data(prev)
                merged.merge(sd)
                t.entries[trace_id] = merged
            else:
                t.entries[trace_id] = sd
            t.epoch += 1
            n = len(t.entries)
        from tempo_tpu.observability import metrics as obs

        obs.live_tier_entries.set(n, tenant=tenant)

    def mark_cut(self, tenant: str, trace_ids) -> None:
        """Cut traces leave the live stage — they are now WAL-head
        entries, scanned there (StreamingSearchBlock's gate-on path)."""
        if not self.enabled:
            return
        with self._lock:
            t = self._tenants.get(tenant)
            if t is None:
                return
            evicted = 0
            for tid in trace_ids:
                if t.entries.pop(tid, None) is not None:
                    evicted += 1
            if evicted:
                t.epoch += 1
            n = len(t.entries)
        if evicted:
            from tempo_tpu.observability import metrics as obs

            obs.live_tier_evictions.inc(evicted, reason="cut")
            obs.live_tier_entries.set(n, tenant=tenant)

    def drop_tenant(self, tenant: str) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._tenants.pop(tenant, None)

    # ---- poll-visibility (fed by TempoDB.poll on the reader) ----

    def mark_poll_visible(self, metas_by_tenant: dict) -> None:
        """Record the backend blocks the reader's poll made visible.
        The ingester's recently-flushed leg consults this set to retire
        a flushed block EARLY (the reader leg now answers for it) —
        without it, both legs scan the block for the full 300s
        recently-flushed window and dedupe eats the duplicates."""
        if not self.enabled:
            return
        with self._lock:
            for tenant, ms in metas_by_tenant.items():
                self._tenant(tenant).visible = {
                    m.block_id for m in ms}

    def poll_visible(self, tenant: str, block_id: str) -> bool:
        if not self.enabled:
            return False
        with self._lock:
            t = self._tenants.get(tenant)
            return t is not None and block_id in t.visible

    # ---- search ----

    def search(self, tenant: str, req, results) -> bool:
        """Kernel-scan the tenant's live stage. Returns True when the
        hot tier answered (the caller must NOT run the legacy per-entry
        walk), False on gate-off or overflow (stage past max_entries —
        the caller falls back to the walk and the fallback is counted)."""
        if not self.enabled:
            return False
        with self._lock:
            t = self._tenants.get(tenant)
            if t is None:
                return True  # no live traces: nothing to scan
            if len(t.entries) > self.max_entries:
                from tempo_tpu.observability import metrics as obs

                obs.live_tier_scans.inc(result="fallback_overflow")
                return False
            entries = [t.entries[tid] for tid in sorted(t.entries)]
            epoch = t.epoch
            stage = t.stage
        if not entries:
            return True
        from tempo_tpu.observability import metrics as obs

        handled = scan_search_data(entries, req, results, stage, epoch)
        obs.live_tier_scans.inc(result="scan" if handled else "fallback")
        return handled

    # ---- tail subscriptions ----

    def subscribe(self, tenant: str, req,
                  max_queue: int = 256) -> TailSubscription | None:
        """Register a standing query. None = per-tenant cap reached
        (the caller surfaces 429-style rejection)."""
        if not self.enabled:
            return None
        with self._lock:
            t = self._tenant(tenant)
            t.subs = [s for s in t.subs if not s.closed]
            if len(t.subs) >= self.max_subscriptions:
                from tempo_tpu.observability import metrics as obs

                obs.live_tail_dropped.inc(reason="cap", tenant=tenant)
                return None
            sub = TailSubscription(tenant, req, max_queue=max_queue)
            t.subs.append(sub)
            n = len(t.subs)
        from tempo_tpu.observability import metrics as obs

        obs.live_tail_subscriptions.set(n, tenant=tenant)
        return sub

    def unsubscribe(self, sub: TailSubscription) -> None:
        if not self.enabled:
            return
        sub.close()
        with self._lock:
            t = self._tenants.get(sub.tenant)
            if t is None:
                return
            t.subs = [s for s in t.subs if s is not sub and not s.closed]
            n = len(t.subs)
        from tempo_tpu.observability import metrics as obs

        obs.live_tail_subscriptions.set(n, tenant=sub.tenant)

    def has_subscribers(self, tenant: str) -> bool:
        if not self.enabled:
            return False
        with self._lock:
            t = self._tenants.get(tenant)
            return bool(t and t.subs)

    def notify_push(self, tenant: str, trace_id: bytes, raw: bytes) -> None:
        """Evaluate standing queries against one push micro-batch
        member. The decode happens at most once per push and ONLY when
        the tenant has live subscriptions; structural predicates
        evaluate via eval_host (search_data_matches), the same route the
        gate-off walk uses."""
        if not self.enabled:
            return
        with self._lock:
            t = self._tenants.get(tenant)
            subs = list(t.subs) if t else []
        if not subs or not raw:
            return
        try:
            sd = decode_search_data(raw, trace_id)
        except Exception:  # noqa: BLE001 — corrupt push: nothing to notify
            return
        meta = None
        from tempo_tpu.observability import metrics as obs

        for sub in subs:
            if sub.closed:
                continue
            if search_data_matches(sd, sub.req):
                if meta is None:
                    from .streaming import _meta_from_sd

                    meta = _meta_from_sd(sd)
                sub.offer(meta)
                obs.live_tail_notifications.inc(tenant=tenant)


LIVE_TIER = LiveTier()
