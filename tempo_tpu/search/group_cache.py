"""The staged-group cache: HBM over host RAM, under one budget rule.

Where a group of the batcher's plan lives between searches: the HBM
tier (one `_CachedBatch` a resident group: LRU, pinned while a search
has it in flight, with its per-batch prepare memo), the host-RAM tier
under it (a re-stage is then one H2D copy), who is staging what, and
the evictions an ownership rebalance had to defer.

Every byte enters or leaves the HBM budget through `_insert_locked`,
`_remove_locked` (whole entries) or `charge_locked` (what a search adds
to an entry that may have been evicted meanwhile): the running totals
are written there and nowhere else (`tests/test_evict_served.py`). The
`?agg=` key column of a group (`agg_staged`) is such an addition: built
and put by the group's first aggregating search, under one flight, and
given back by the group's eviction.

One lock, `group_lock`, guards all of it, and the batcher's plan cache,
prune memo and interest counts with it: the search loop decides under
ONE hold what is resident, what is being staged and what the header
memo knows. A method named `*_locked` is called with it held.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field

from tempo_tpu import robustness
from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import tracing

from .analytics import build_agg_stage
from .ownership import OWNERSHIP
from .pipeline import MASK_BYTES
from .structural import span_device_bytes


@dataclass(eq=False)         # an entry is itself: `==` would compare arrays
class _CachedBatch:
    batch: object           # multiblock.BlockBatch
    nbytes: int
    # unpacked-layout equivalent of nbytes (the logical side of the
    # packed-residency accounting split; == nbytes when packing is off).
    # Fixed at stage time so add/remove stay symmetric.
    logical: int = 0
    jobs: list = field(default_factory=list)
    # the prepare memo: everything O(group-size) that depends only on
    # the request's predicate (per-block compile tables, metric sums),
    # so that a repeated query pays no O(blocks) python. Keyed by the
    # full predicate signature; bounded LRU (`memo_put`). An eviction
    # hands what of it holds no device state to the host-tier entry
    # (`_keep_memo_locked`) and the group's next stage starts from it.
    query_cache: OrderedDict = field(default_factory=OrderedDict)
    # HBM pin count: searches holding this batch in flight, from when
    # they take it (`staged(pin=True)`, their look-ahead included) to
    # the drain of THEIR dispatch over it — not to the end of the
    # search, or a tenant-wide search pins the tenant and the budget
    # bounds nothing. Eviction skips pinned entries: their device
    # arrays live on in the in-flight references anyway, and the budget
    # would pay twice when the next query re-stages the batch
    pins: int = 0
    # device hit masks ([G, T, Vmax] stacks) the prepare memo pins, part
    # of `nbytes` and published as probe_mask_bytes{held_by="memo"}
    mask_bytes: int = 0
    # the ?agg= key column (analytics.AggStage) once an aggregating
    # search has staged it (`GroupCache.agg_staged`), and its HBM, part
    # of `nbytes` and published as tempo_search_agg_staged_bytes
    agg_stage: object = None
    agg_bytes: int = 0


_QUERY_CACHE_MAX = 32


def _dict_bytes(batch) -> int:
    """HBM held by a batch's staged device-probe dictionaries."""
    return sum(int(d.nbytes)
               for d in getattr(batch, "staged_dicts", {}).values())


def _span_bytes(batch) -> int:
    """HBM held by a batch's structural span columns."""
    return span_device_bytes(getattr(batch, "span_device", None))


def _dead(gkey: tuple, live_block_ids: set) -> bool:
    return any(jk[0] not in live_block_ids for jk in gkey)


class GroupCache:
    """Staged groups by group key (`tuple(j.key for j in group)`), in
    HBM and in host RAM, each tier under its byte budget. Thread-safe;
    one instance per BlockBatcher."""

    def __init__(self, engine, cache_bytes: int,
                 host_cache_bytes: int | None, io_workers: int):
        self.engine = engine
        self.cache_bytes = cache_bytes
        if host_cache_bytes is None:
            # auto-size: the host tier retains stacked batches (and pins
            # their source pages), so an unconditional 32 GB default
            # OOM-kills small hosts — cap at half of physical RAM
            try:
                phys = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
            except (ValueError, OSError, AttributeError):
                phys = 16 << 30
            host_cache_bytes = min(32 << 30, phys // 2)
        self.host_cache_bytes = host_cache_bytes
        self.io_workers = io_workers
        self.group_lock = threading.Lock()
        self._cache: OrderedDict[tuple, _CachedBatch] = OrderedDict()
        self._cache_total = 0
        self._cache_peak = 0        # high water of _cache_total, as published
        self._probe_dict_total = 0  # staged-dict bytes across _cache
        self._span_total = 0        # span-column bytes across _cache
        self._agg_total = 0         # ?agg= key-column bytes across _cache
        # logical (unpacked-layout) bytes across both tiers — the other
        # half of the packed-residency accounting split: budgets charge
        # PHYSICAL bytes (that is why packing fits more blocks), the
        # logical gauges say how much unpacked data those bytes carry
        self._cache_logical = 0
        self._host_logical = 0
        # the host-RAM tier: stacked numpy batches, budgeted separately.
        # An HBM eviction leaves the host copy
        self._host_cache: OrderedDict[tuple, object] = OrderedDict()
        self._host_total = 0
        # host-fallback CPU-pinned array copies (host_scan's per-batch
        # memo), charged to the host budget separately so eviction can
        # release exactly what was charged
        self._cpu_staged_bytes: dict[tuple, int] = {}
        self._staging: dict[tuple, threading.Event] = {}
        # ownership rebalance evictions deferred while a search pins the
        # batch: gkey -> the exact entry to drop at unpin. Keyed by entry
        # IDENTITY at eviction time so a marker gone stale (the LRU got
        # there first, or a re-stage replaced the object) is discarded
        # instead of double-subtracting the budget
        self._evict_deferred: dict[tuple, _CachedBatch] = {}

    # ---- the budget rule: the only writers of the running totals ----

    def _insert_locked(self, gkey: tuple, entry: _CachedBatch) -> None:
        """`entry` becomes the group's resident entry, charged whole;
        the entry it replaces leaves first."""
        self._remove_locked(gkey)
        self._cache[gkey] = entry
        self._cache_total += entry.nbytes
        self._cache_logical += entry.logical
        self._probe_dict_total += _dict_bytes(entry.batch)
        self._span_total += _span_bytes(entry.batch)
        self._agg_total += entry.agg_bytes
        MASK_BYTES.add("memo", entry.mask_bytes)

    def _remove_locked(self, gkey: tuple) -> _CachedBatch | None:
        """The group's resident entry leaves the tier and the budget;
        returns it, or None where the group was not resident."""
        old = self._cache.pop(gkey, None)
        if old is not None:
            self._cache_total -= old.nbytes
            self._cache_logical -= old.logical
            self._probe_dict_total -= _dict_bytes(old.batch)
            self._span_total -= _span_bytes(old.batch)
            self._agg_total -= old.agg_bytes
            MASK_BYTES.add("memo", -old.mask_bytes)
        return old

    def charge_locked(self, gkey: tuple, entry: _CachedBatch, *,
                      params: int = 0, mask: int = 0, agg: int = 0) -> None:
        """A search adds device memory to `entry` (a predicate's
        uploaded tables, a hit mask the memo keeps, the group's ?agg=
        key column) or takes it back
        (the memo dropped the predicate). The entry always carries the
        change, so its eviction gives back what it holds; the shared
        totals only where it is still the group's resident entry — an
        eviction meanwhile removed `entry.nbytes` wholesale, and
        adjusting again would drift the budget by memory no eviction
        can reclaim. What a charge pushes over budget goes now."""
        if not (params or mask or agg):
            return
        entry.nbytes += params + mask + agg
        entry.mask_bytes += mask
        entry.agg_bytes += agg
        if self._cache.get(gkey) is entry:
            self._cache_total += params + mask + agg
            self._agg_total += agg
            MASK_BYTES.add("memo", mask)
            if params + mask + agg > 0:
                self._evict_hbm_locked()

    def _insert_host_locked(self, gkey: tuple, host) -> None:
        self._host_cache[gkey] = host
        self._host_total += host.nbytes
        self._host_logical += host.logical_nbytes
        self._evict_host_locked()
        self._publish_gauges_locked()

    def _remove_host_locked(self, gkey: tuple) -> None:
        """A host-tier entry leaves: its nbytes and any CPU-pinned
        fallback copies host_scan memoized on it."""
        oldh = self._host_cache.pop(gkey)
        self._host_total -= oldh.nbytes + self._cpu_staged_bytes.pop(gkey, 0)
        self._host_logical -= oldh.logical_nbytes

    def charge_cpu_copies(self, gkey: tuple, host) -> None:
        """The CPU-pinned copies host_scan memoized on `host` are real
        RAM: charge them to the host-tier budget (evicting the entry
        releases both). Delta-charged: the span-column memo
        (_cpu_span_staged) can appear on a LATER structural query after
        the cat arrays were already charged, and it must not pin
        unaccounted RAM."""
        cpu_b = sum(
            int(a.nbytes)
            for memo in (getattr(host, "_cpu_staged", None),
                         getattr(host, "_cpu_span_staged", None))
            if memo is not None for a in memo.values())
        if not cpu_b:
            return
        with self.group_lock:
            if self._host_cache.get(gkey) is host:
                prev = self._cpu_staged_bytes.get(gkey, 0)
                if cpu_b > prev:
                    self._cpu_staged_bytes[gkey] = cpu_b
                    self._host_total += cpu_b - prev
                    self._evict_host_locked()
                    self._publish_gauges_locked()

    # ---- eviction ----

    def _publish_gauges_locked(self) -> None:
        """Occupancy gauges for /metrics: HBM + host tier bytes, and
        the HBM share held by staged device-probe dictionaries across
        resident batches. All are running totals — this must stay O(1),
        it runs on every stage/evict under the global lock."""
        obs.hbm_cache_bytes.set(self._cache_total)
        if self._cache_total > self._cache_peak:
            # what the gauge above ever showed: a scrape at the ends of
            # an interval cannot see an overshoot inside it
            self._cache_peak = self._cache_total
            obs.hbm_cache_peak_bytes.set(self._cache_peak)
        obs.host_cache_bytes.set(self._host_total)
        obs.probe_dict_bytes.set(self._probe_dict_total)
        obs.structural_span_bytes.set(self._span_total)
        obs.agg_staged_bytes.set(self._agg_total)
        obs.hbm_logical_bytes.set(self._cache_logical)
        obs.host_logical_bytes.set(self._host_logical)

    def _evict_host_locked(self) -> None:
        """LRU-evict host-tier batches until the budget holds."""
        while (self._host_total > self.host_cache_bytes
               and len(self._host_cache) > 1):
            self._remove_host_locked(next(iter(self._host_cache)))
            obs.batch_cache_events.inc(result="host_evict")

    def _keep_memo_locked(self, gkey: tuple, old: _CachedBatch) -> None:
        """An evicted batch's prepare memo outlives it on the host-tier
        entry. The memo is host work (the per-block predicate compile),
        and a tenant larger than its HBM budget would pay it again at
        every re-stage: on a v5e that was 39 % of all lookups and the
        largest span of a search. What holds device state stays behind:
        a predicate's uploaded tables (HBM the eviction just gave back;
        the next dispatch uploads them again), and whole entries
        compiled against the batch's staged dictionaries or a
        structural plan."""
        host = self._host_cache.get(gkey)
        if host is None:
            return
        host.query_memo = OrderedDict(
            (sig, {k: v for k, v in pre.items()
                   if k not in ("device_params", "device_params_bytes")})
            for sig, pre in old.query_cache.items()
            if pre.get("val_hits") is None and pre.get("structural") is None)

    def _drop_hbm_locked(self, gkey: tuple) -> None:
        """Evict one staged batch: the eviction shared by the LRU, the
        ownership rebalance and the deferred-at-unpin sweep."""
        old = self._remove_locked(gkey)
        if old is None:
            return
        self._keep_memo_locked(gkey, old)
        obs.batch_cache_events.inc(result="evict")
        obs.hbm_evicted_bytes.inc(old.nbytes)

    def _evict_hbm_locked(self) -> None:
        """LRU-evict staged batches until the HBM budget holds. Pinned
        entries (actively scanned by some search) are skipped: evicting
        them reclaims nothing (the in-flight dispatch pins the device
        arrays) and guarantees an immediate re-stage."""
        while self._cache_total > self.cache_bytes and len(self._cache) > 1:
            victim = next((k for k, v in self._cache.items()
                           if v.pins <= 0), None)
            if victim is None:
                break  # everything pinned: over budget until a drain
            self._drop_hbm_locked(victim)
        self._publish_gauges_locked()

    def _run_deferred_evictions_locked(self) -> None:
        """Ownership-rebalance evictions deferred while pinned run NOW
        (at unpin) — exactly once: a marker whose cache entry is gone or
        replaced (an LRU eviction or a re-stage beat us here) is
        discarded without touching the budget, so a rebalance and an LRU
        eviction targeting the same batch can never double-subtract its
        bytes."""
        if not self._evict_deferred:
            return
        for gkey, entry in list(self._evict_deferred.items()):
            if self._cache.get(gkey) is not entry:
                del self._evict_deferred[gkey]  # stale: already gone
                continue
            if entry.pins > 0:
                continue  # another search still holds it
            self._drop_hbm_locked(gkey)
            del self._evict_deferred[gkey]
            obs.hbm_owner_rebalance_evictions.inc(result="dropped")

    def rebalance_ownership(self) -> dict:
        """Treat an ownership rebalance as a PLACEMENT change for the
        HBM cache: every resident batch whose group this member no
        longer owns is dropped now, or — while a search pins it —
        deferred to the unpin sweep. Host-tier entries stay: the
        non-owner route serves from exactly that tier, so dropping them
        would re-pay IO+decompress on the next routed-away query."""
        if not OWNERSHIP.enabled:
            return {"hbm_dropped": 0, "hbm_deferred": 0}
        # load-aware: demote heat-promoted groups whose rate decayed
        # below the hysteresis floor FIRST, so a stale replica's
        # residency falls out through the ordinary owns_group walk below
        # (same dropped/deferred path a placement move takes)
        OWNERSHIP.sweep()
        dropped = deferred = 0
        with self.group_lock:
            for gkey in list(self._cache):
                if OWNERSHIP.owns_group(gkey):
                    self._evict_deferred.pop(gkey, None)  # owned again:
                    # a pending deferral from an older generation is void
                    continue
                entry = self._cache[gkey]
                if entry.pins > 0:
                    # count a deferral once per BATCH, not once per
                    # rebalance: a batch pinned across several
                    # membership flips re-arrives here each time
                    if self._evict_deferred.get(gkey) is not entry:
                        deferred += 1
                    self._evict_deferred[gkey] = entry
                else:
                    self._evict_deferred.pop(gkey, None)
                    self._drop_hbm_locked(gkey)
                    dropped += 1
            self._publish_gauges_locked()
        if dropped:
            obs.hbm_owner_rebalance_evictions.inc(dropped, result="dropped")
        if deferred:
            obs.hbm_owner_rebalance_evictions.inc(deferred,
                                                  result="deferred")
        return {"hbm_dropped": dropped, "hbm_deferred": deferred}

    def invalidate(self, live_block_ids: set[str]) -> None:
        """Drop cached batches containing blocks no longer in the
        blocklist (called from the poll loop) — both HBM and host tiers."""
        with self.group_lock:
            for k in [k for k in self._cache if _dead(k, live_block_ids)]:
                self._remove_locked(k)
                # a pending rebalance deferral for a dead block's batch
                # is satisfied by this removal — keeping the marker
                # would double-evict whatever re-stages under the key
                self._evict_deferred.pop(k, None)
            for k in [k for k in self._host_cache
                      if _dead(k, live_block_ids)]:
                self._remove_host_locked(k)
            self._publish_gauges_locked()

    # ---- pins ----

    def unpin_locked(self, entries) -> None:
        """Give back pins taken by `staged(pin=True)` or
        `resident_locked(pin=True)`. What the pins held over budget
        goes now: first the ownership-rebalance deferrals
        (exactly-once, identity-checked), then ordinary LRU pressure."""
        for c in entries:
            c.pins -= 1
        self._run_deferred_evictions_locked()
        self._evict_hbm_locked()

    def unpin_unused(self, fut) -> None:
        """Done-callback of a look-ahead no search came back for."""
        if fut.exception() is None:
            entry = fut.result()     # done: returns at once
            with self.group_lock:
                self.unpin_locked((entry,))

    def resident_locked(self, key: tuple, pin: bool):
        """The group's resident entry, touched, counted as a hit and,
        with `pin`, pinned; None where the group is not resident."""
        hit = self._cache.get(key)
        if hit is not None:
            self._cache.move_to_end(key)
            obs.batch_cache_events.inc(result="hit")
            if pin:
                hit.pins += 1
        return hit

    # ---- staging ----

    def _claim_stage(self, ev_key: tuple, find_locked):
        """One flight a stage: (what `find_locked()` finds under the
        lock, None), or (None, the event of the stage of `ev_key` this
        caller now owns and ends with `_stage_done`). While another
        thread stages `ev_key`, wait for it and look again rather than
        duplicating the IO+decompress+H2D (and transiently doubling HBM
        for the batch)."""
        while True:
            with self.group_lock:
                found = find_locked()
                if found is not None:
                    return found, None
                ev = self._staging.get(ev_key)
                if ev is None:
                    ev = self._staging[ev_key] = threading.Event()
                    return None, ev
            ev.wait()

    def _stage_done(self, ev_key: tuple, ev) -> None:
        with self.group_lock:
            self._staging.pop(ev_key, None)
        ev.set()

    def staged(self, group: list, pin: bool = False,
               parent=None) -> _CachedBatch:
        """The group's staged batch, from the HBM cache or staged now.
        `pin` takes a pin under the same lock that finds or inserts the
        entry, so no eviction pass can drop what the caller is about to
        scan (its own insert's included); the caller gives it back
        through `unpin_locked`. `parent` is the span context a
        look-ahead thread writes `batcher.place` under (a stage on the
        searching thread finds its `batcher.Search` current)."""
        key = tuple(j.key for j in group)
        hit, ev = self._claim_stage(
            key, lambda: self.resident_locked(key, pin))
        if hit is not None:
            return hit
        try:
            host = self._load_host(key, group)
            # H2D only on the hot path; watchdog-bounded — a staging put
            # into a device that stopped answering raises DeviceFault
            # (breaker fault booked) and the caller answers through the
            # host route
            def put():
                """The put alone, fenced (place_batch waits for the
                arrays): H2D apart from `_load_host`'s IO and stacking.
                `batcher.place` is stamped and written by the thread
                that does the put, which under the dispatch watchdog is
                one of its workers: the span's `thread.cpu_ns` is then
                the put's own, not that of the caller asleep on it."""
                if tracing.get_tracer() is None:
                    return self.engine.place(host)
                t0, c0 = tracing.now_ns(), tracing.cpu_ns()
                batch = self.engine.place(host)
                tracing.record_span(
                    "batcher.place", t0, tracing.now_ns(),
                    parent=parent or tracing.current_span().context,
                    cpu_start_ns=c0, cpu_end_ns=tracing.cpu_ns(),
                    bytes=int(batch.device_nbytes), blocks=len(group))
                return batch

            batch = robustness.GUARD.run("h2d", put)
            if batch.span_device is not None:
                # span rows staged, live and pad: counters alone, so a
                # flat search that stages a span-bearing group writes
                # nothing new into its trace (PERF.md section 7 h11)
                rows = int(batch.span_device["span_trace"].shape[0])
                live = sum(b.n_spans for b in batch.blocks)
                obs.structural_span_rows.inc(live, kind="live")
                obs.structural_span_rows.inc(rows - live, kind="pad")
            # batch.nbytes covers the stacked page arrays AND any staged
            # probe dictionaries — both live in HBM under this budget
            # (physical/packed bytes; the logical twin feeds the gauges)
            entry = _CachedBatch(batch=batch, nbytes=int(batch.nbytes),
                                 logical=int(batch.logical_nbytes),
                                 jobs=list(group), pins=int(pin))
            with self.group_lock:
                obs.batch_cache_events.inc(result="miss")
                # what the last eviction of this group kept of its memo
                if host.query_memo is not None:
                    entry.query_cache, host.query_memo = (
                        host.query_memo, None)
                self._insert_locked(key, entry)
                self._evict_hbm_locked()
            return entry
        finally:
            self._stage_done(key, ev)

    def agg_staged(self, gkey: tuple, entry: _CachedBatch):
        """The ?agg= key column of `entry`'s group (analytics.AggStage),
        on the device: built and put once an entry by the group's first
        aggregating search, one flight (a second first search waits for
        the first's), and charged to the entry when it is there, so the
        budget and tempo_search_hbm_cache_bytes hold it from then on and
        the group's eviction gives it back. Charged here and not when
        the group is staged: a group nobody aggregates over pays no HBM
        and no build for the gate being on. The caller holds a pin on
        `entry`; the put is watchdog-bounded like the group's own."""
        ev_key = ("agg",) + gkey
        stage, ev = self._claim_stage(ev_key, lambda: entry.agg_stage)
        if stage is not None:
            return stage
        try:
            t0 = time.perf_counter()
            batch = entry.batch
            with tracing.start_span("analytics.stage") as span:
                stage = build_agg_stage(
                    batch.blocks, int(batch.device["entry_valid"].shape[0]),
                    batch.blocks[0].geometry.entries_per_page)
                nbytes = int(stage.host.nbytes)
                robustness.GUARD.run(
                    "h2d", lambda: self.engine.place_agg(stage))
                # the launches read the device's copy from here on
                stage.host = None
                span.set_attributes(bytes=nbytes, blocks=len(batch.blocks))
            obs.agg_stage_seconds.observe(time.perf_counter() - t0)
            with self.group_lock:
                entry.agg_stage = stage
                self.charge_locked(gkey, entry, agg=nbytes)
            return stage
        finally:
            self._stage_done(ev_key, ev)

    def _load_host(self, key: tuple, group: list):
        """Host-tier staging (IO + decompress + stack, NO device put):
        the first half of `staged`, and the WHOLE staging for the
        breaker's host-fallback route."""
        with self.group_lock:
            host = self._host_cache.get(key)
            if host is not None:
                self._host_cache.move_to_end(key)
        if host is None:
            # load host pages outside the lock (IO + decompress
            # dominate)
            import concurrent.futures

            if len(group) > 1:
                with concurrent.futures.ThreadPoolExecutor(
                    max_workers=min(self.io_workers, len(group))
                ) as ex:
                    pages = list(ex.map(lambda j: j.pages_fn(), group))
            else:
                pages = [group[0].pages_fn()]
            host = self.engine.stage_host(pages)
            with self.group_lock:
                self._insert_host_locked(key, host)
            obs.batch_cache_events.inc(result="host_miss")
        else:
            obs.batch_cache_events.inc(result="host_hit")
        return host

    def host_batch(self, group: list):
        """The host-fallback route's staging: host tier only, deduped
        against concurrent fallers the same way `staged` dedupes device
        staging (a distinct event key — a host-route stage must not
        block behind a device stage wedging on the same group)."""
        key = tuple(j.key for j in group)
        ev_key = ("host",) + key
        _found, ev = self._claim_stage(
            ev_key, lambda: self._host_cache.get(key))
        try:
            return self._load_host(key, group)   # resident: hit counters
        finally:
            if ev is not None:
                self._stage_done(ev_key, ev)

    # ---- the per-batch prepare memo ----

    def memo_get(self, entry, sig: tuple):
        """What the memo of `entry` (a resident entry or, on the host
        route, the group's host-tier entry) keeps for predicate `sig`,
        touched; or None."""
        with self.group_lock:
            pre = entry.query_cache.get(sig)
            if pre is not None:
                entry.query_cache.move_to_end(sig)
        return pre

    def memo_put(self, gkey: tuple, entry, sig: tuple, pre: dict) -> None:
        """Keep a predicate's prepared tables on its batch. A hit mask
        the memo keeps is HBM like a predicate's uploaded tables:
        charged to the batch, so the budget sees it and an eviction
        gives it back. One charge, the mask less what the predicates
        the memo's LRU drops held, then the one eviction pass. (The
        host route's predicates hold no device memory: nothing is
        charged to a host-tier entry.)"""
        mask = pre["mask_bytes"] = int(
            getattr(pre.get("val_hits"), "nbytes", 0))
        params = 0
        with self.group_lock:
            entry.query_cache[sig] = pre
            while len(entry.query_cache) > _QUERY_CACHE_MAX:
                _, old = entry.query_cache.popitem(last=False)
                mask -= old["mask_bytes"]
                params -= old.get("device_params_bytes", 0)
            self.charge_locked(gkey, entry, params=params, mask=mask)

    def memo_params(self, gkey: tuple, entry: _CachedBatch, pre: dict,
                    device_params) -> None:
        """A dispatch uploaded `pre`'s query tables: the memo keeps
        them for the predicate's next dispatch, and they live in HBM,
        so they are charged to the batch — the budget sees
        per-predicate device memory, not just page arrays. On a mesh
        they are replicated: every device holds the whole of each
        (`nbytes` is its logical size), and the budget is one sum over
        the mesh's devices."""
        dpb = int(sum(getattr(a, "nbytes", 0) for a in device_params)
                  ) * self.engine.n_shards
        with self.group_lock:
            if pre.get("device_params") is None:
                pre["device_params"] = device_params
                pre["device_params_bytes"] = dpb
                self.charge_locked(gkey, entry, params=dpb)

    # ---- reads: the `*_locked` three for the search loop, which holds
    # the lock; the rest for `prewarm`, `trace_report.py` and the tests ----

    def is_resident_locked(self, gkey: tuple) -> bool:
        return gkey in self._cache

    def is_staging_locked(self, gkey: tuple) -> bool:
        return gkey in self._staging

    def in_host_tier_locked(self, gkey: tuple) -> bool:
        return gkey in self._host_cache

    def resident(self, gkey: tuple) -> _CachedBatch | None:
        """The group's resident entry as it stands, untouched."""
        with self.group_lock:
            return self._cache.get(gkey)

    def snapshot(self) -> dict:
        """Both tiers under one hold, least recently used first:
        `entries` gkey -> (nbytes, pins, mask_bytes) of the resident
        ones, `host` gkey -> host-tier entry, `staging` the keys being
        staged now (a host-route stage's begins with "host"), and the
        byte totals."""
        with self.group_lock:
            return {"entries": {k: (v.nbytes, v.pins, v.mask_bytes)
                                for k, v in self._cache.items()},
                    "host": dict(self._host_cache),
                    "staging": list(self._staging),
                    "hbm_bytes": self._cache_total,
                    "hbm_peak_bytes": self._cache_peak,
                    "host_bytes": self._host_total}

    def ownership_residency(self) -> list:
        """Per-resident-batch ownership view for /debug/ownership: which
        placement group each staged batch anchors to, who owns it, and
        whether a deferred rebalance eviction is pending on it."""
        with self.group_lock:
            rows = [(k, v.nbytes, v.pins, k in self._evict_deferred)
                    for k, v in self._cache.items()]
        out = []
        for gkey, nbytes, pins, pending in rows:
            anchor = str(gkey[0][0])
            out.append({
                "anchor_block": anchor,
                "placement_group": OWNERSHIP.group_of(anchor),
                "owner": OWNERSHIP.owner_of(anchor),
                "owned": OWNERSHIP.owns_block(anchor),
                "jobs": len(gkey),
                "bytes": int(nbytes),
                "pins": int(pins),
                "deferred_evict": pending,
                # residency held through a heat-promoted replica set
                # rather than plain ownership (owner included while
                # the group is promoted)
                "replica": OWNERSHIP.is_replica(anchor),
            })
        return out

    def debug_stats_locked(self) -> dict:
        """The cache's half of /debug/scan: occupancy by tier."""
        def tier(entries, nbytes, logical, budget):
            return {"batches": len(entries), "bytes": nbytes,
                    "logical_bytes": logical, "budget_bytes": budget}

        return {
            "hbm_cache": tier(self._cache, self._cache_total,
                              self._cache_logical, self.cache_bytes),
            "host_cache": tier(self._host_cache, self._host_total,
                               self._host_logical, self.host_cache_bytes)}
