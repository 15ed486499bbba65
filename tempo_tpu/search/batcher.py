"""Serving-path batch scanning: many blocks (or page ranges), few kernels.

This is where the TPU economics land in the serving path. The reference's
production search IS its job fan-out — one goroutine per 10 MiB page range
(modules/frontend/searchsharding.go:163-306, tempodb/pool) — because on
CPU the per-job cost is the scan itself. On TPU the per-dispatch overhead
(host sync + kernel launch) dwarfs the scan of a single block, so the
batcher inverts the shape: jobs GROUP into batches
whose pages stack along the device page axis and scan in ONE kernel call
(`multiblock.batch_scan_kernel`; with a mesh, under shard_map, where
collectives replace the Results funnel).

Properties the grouping keeps:
- **stable AND churn-local**: jobs sort by (header start time, block
  id, page range) and group boundaries are content-defined — a job
  starts a new group based only on a stable hash of its own key (like
  content-defined chunking in dedup stores) — so the same blocklist
  yields the same groups query after query, and a block arriving or
  leaving the blocklist reshapes only its own neighborhood up to the
  next hash anchor: O(1) cached batches invalidate per poll instead of
  every group downstream of the new block's sort position.
- **cut in time, like the windows**: block ids are uuids, so an id
  order scatters the blocks a time window keeps over every group and a
  group with one live block is staged whole. In start-time order a
  window's blocks are neighbours: it touches the groups its hours lie
  in, the header prune skips the others before any staging, a flushed
  block lands in the newest group, and under an HBM budget smaller
  than the tenant the LRU keeps the hours that are asked for.
- **bucketed**: only jobs sharing page geometry (E entries/page, C kv
  slots) stack together — static shapes per bucket mean XLA compiles once
  per (bucket, n_terms, top_k).
- **sized per device**: a group is what ONE device scans in a launch.
  It closes at `max_batch_pages` pages for each device that reads it
  (`group_cap`: `max_batch_pages * engine.n_shards`, read when a plan is
  made), so a mesh of s devices groups s times as many pages and every
  device scans in one launch exactly what a one-chip engine scans in
  one. The host's price per launch is fixed; a cap counted over the
  whole mesh bought 1/s of the device work for it.
- **prune-aware without cache churn**: header- or dictionary-pruned jobs
  stay IN the staged batch (composition never depends on the query); the
  compiled query neutralizes them (key id -1 → no page can match) and
  their entries are subtracted from inspected counts on the host.
- **pipelined with early quit**: group i+1 stages + dispatches while
  group i's results transfer; dispatch stops once the result limit is met
  (reference results.go:38-78 quit channel).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field

import numpy as np

from tempo_tpu import robustness
from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import profile
from tempo_tpu.observability import tracing

from . import query_stats
from . import structural as _structural
from .analytics import ANALYTICS, agg_requested
from .engine import DEFAULT_TOP_K, fetch_scan_out, resolve_top_k, start_fetch
from .ownership import OWNERSHIP
from .multiblock import (
    WIDE_RANGES, MultiBlockEngine, block_bucket, compile_multi,
    stack_queries,
)
from .pipeline import MASK_BYTES, block_header_skip_reason, probe_summary
from .results import SearchResults


def host_scan(host, mq, top_k: int):
    """The host route's execution (breaker fallback AND the ownership
    layer's non-owner serve): run the SAME batch_scan_kernel over the
    host-tier stacked arrays, pinned to the CPU backend — no
    wedged-device array is ever touched, no duplicate HBM copy is ever
    staged on a non-owner. Because it is
    the same kernel over the same padded shapes and the same compiled
    predicate semantics (host range tables; the device hit-mask path
    yields identical matches), the results are byte-identical to the
    device dispatch, ties included: equal start seconds resolve to the
    lowest flat index on every path (masked_topk).

    The CPU-staged arrays memoize on the HostBatch (`_cpu_staged`), so
    a wedged-device soak re-stages each batch once, not per query; the
    memo dies with the host-tier entry. Returns the drain-format host
    tuple (count, inspected, scores, idx), plus the dense ?agg= counts
    when the query carries an agg_stage — the same integer reduction
    the device kernels run, so the host route's aggregate is
    byte-identical by construction."""
    import jax.numpy as jnp

    from .engine import cpu_pinned
    from .multiblock import batch_scan_kernel

    t0 = time.perf_counter()
    with cpu_pinned():
        dev = getattr(host, "_cpu_staged", None)
        if dev is None:
            dev = {k: jnp.asarray(v) for k, v in host.cat.items()}
            host._cpu_staged = dev
        tk = jnp.asarray(mq.term_keys)
        vr = jnp.asarray(mq.val_ranges)
        # structural predicate on the host route: the host-only compile
        # produced range tables (no device mask is ever touched) and the
        # span columns stage once per batch on the CPU backend — same
        # kernel, same plan, byte-identical verdicts
        st = getattr(mq, "structural", None)
        plan = s_tables = span_dev = None
        if st is not None:
            plan = st.plan
            s_tables = tuple(
                (jnp.asarray(t) if t is not None and not hasattr(
                    t, "devices") else t) for t in st.tables())
            span_host = getattr(host, "span_cat", None)
            if span_host is not None:
                span_dev = getattr(host, "_cpu_span_staged", None)
                if span_dev is None:
                    span_dev = {k: jnp.asarray(v)
                                for k, v in span_host.items()}
                    host._cpu_span_staged = span_dev
        # ?agg= composite keys, CPU-pinned and memoized like the page
        # arrays above (the AggStage itself is shared with the device
        # route via the batch memo — only the placement differs)
        agg_stage = getattr(mq, "agg_stage", None)
        agg = entry_agg = None
        if agg_stage is not None:
            agg = agg_stage.n_keys
            entry_agg = getattr(host, "_cpu_agg_staged", None)
            if entry_agg is None:
                entry_agg = host._cpu_agg_staged = agg_stage.cpu()
        out = batch_scan_kernel(
            dev["kv_key"], dev["kv_val"], dev["entry_start"],
            dev["entry_end"], dev["entry_dur"], dev["entry_valid"],
            dev["page_block"], tk, vr, None,
            jnp.uint32(mq.dur_lo), jnp.uint32(min(mq.dur_hi, 0xFFFFFFFF)),
            jnp.uint32(mq.win_start),
            jnp.uint32(min(mq.win_end, 0xFFFFFFFF)),
            None, None, dev.get("entry_dur_res"),
            span_dev, s_tables, entry_agg,
            n_terms=mq.n_terms, top_k=top_k,
            # the host tier stages the SAME packed layout (stack_host
            # packs before the tiers fork), so the fallback kernel
            # unpacks with the batch's own width descriptor
            widths=getattr(host, "widths", None), plan=plan, agg=agg)
        res = fetch_scan_out(out, agg or 0)
    profile.observe_stage("execute", "host_fallback",
                          time.perf_counter() - t0)
    return res


@dataclass
class ScanJob:
    """One schedulable scan unit: a page range of one block's search
    container (whole block = range [0, n_pages))."""
    key: tuple              # (block_id, start_page, n_pages) — cache identity
    pages_fn: object        # () -> ColumnarPages for this range (host)
    header: dict            # search-header rollup (pruning + sizes)
    n_pages: int
    n_entries: int
    geometry: tuple         # (entries_per_page, kv_per_entry) bucket key
    meta: object = None     # BlockMeta, for diagnostics

    @property
    def bytes_est(self) -> int:
        """Share of the block's compressed bytes this job covers — the
        inspected_bytes accounting unit (reference results.go metrics)."""
        total = max(1, self.header.get("n_pages", self.n_pages))
        return int(self.header.get("compressed_size", 0) * self.n_pages / total)


@dataclass(eq=False)         # an entry is itself: `==` would compare arrays
class _CachedBatch:
    batch: object           # multiblock.BlockBatch
    nbytes: int
    # unpacked-layout equivalent of nbytes (the logical side of the
    # packed-residency accounting split; == nbytes when packing is off).
    # Fixed at stage time so add/remove stay symmetric.
    logical: int = 0
    jobs: list = field(default_factory=list)
    # per-query memo: everything O(group-size) that depends only on the
    # request's predicate (header prune, per-block compile tables, metric
    # sums) — repeated queries over a 10K-block blocklist must not pay
    # O(blocks) python per query (VERDICT r2 #1). Keyed by the full
    # predicate signature; bounded LRU. An eviction hands what of it
    # holds no device state to the host-tier entry (`_keep_memo_locked`)
    # and the next stage of the group starts from there.
    query_cache: OrderedDict = field(default_factory=OrderedDict)
    # HBM pin count: searches holding this batch in flight, from when
    # they take it (`_staged(pin=True)`, their look-ahead included) to
    # the drain of THEIR dispatch over it — not to the end of the
    # search, or a tenant-wide search pins the tenant and the budget
    # bounds nothing. Eviction skips pinned entries so budget pressure
    # never drops a batch a request is actively scanning — its device
    # arrays would survive via the in-flight references anyway, but the
    # budget would double-pay when the next query re-stages it
    pins: int = 0
    # device hit masks ([G, T, Vmax] stacks) the prepare memo pins, part
    # of `nbytes` and published as probe_mask_bytes{held_by="memo"}
    mask_bytes: int = 0


_QUERY_CACHE_MAX = 32
_PRUNE_CACHE_MAX = 4096  # (group, predicate) header-prune memos kept


def _predicate_sig(req) -> tuple:
    """Everything about the request that affects pruning/compilation —
    NOT limit (scalar on the MultiQuery, filled per query). The raw
    structural tag rides separately: _tags_sig excludes it (it is not a
    dictionary term), but two requests differing only structurally must
    not share a prepare() memo."""
    from .pipeline import _tags_sig
    from .structural import STRUCTURAL_QUERY_TAG

    return (_tags_sig(req), req.min_duration_ms or 0,
            req.max_duration_ms or 0, req.start or 0, req.end or 0,
            req.tags.get(STRUCTURAL_QUERY_TAG, ""))


class _PendingCoalesce:
    """Queries waiting on one staged batch for the window to close."""

    __slots__ = ("batch", "gen", "items")

    def __init__(self, batch, gen):
        self.batch = batch
        self.gen = gen
        # [(mq, top_k, Future, submit stamp (tracing.now_ns),
        #   QueryStats|None, the submitter's SpanContext|None)]
        self.items = []


class _FusedOut:
    """One fused dispatch's device output, demuxed lazily: the blocking
    D2H sync runs once, on the FIRST waiter's drain thread — never on
    the submitter whose submit() happened to trigger a size flush (that
    thread has its own dispatch loop to run; syncing there would
    serialize its next group behind this group's fetch).

    The sync runs OUTSIDE the lock (lock-order suite: a d2h sync under
    a lock turns a wedged device into a pile-up of threads parked on
    the lock, each burning its own watchdog): the first waiter CLAIMS
    the fetch under the lock, fetches unlocked, publishes via the done
    event; later waiters park on the event, not the lock. A faulted
    fetch publishes its exception to every waiter — one watchdog burn
    for the group instead of one per member (each member's drain then
    resubmits its own query on the host path, as before)."""

    __slots__ = ("_out", "_engine", "_cq", "_host", "_exc", "_claimed",
                 "_done")

    def __init__(self, out, engine, cq):
        # the launch's one device array, and who fetches it: the engine
        # that launched `cq` (one np.asarray, taken apart by row)
        self._out = out
        self._engine = engine
        self._cq = cq
        self._host = None
        self._exc = None
        self._claimed = threading.Lock()
        self._done = threading.Event()

    def host(self) -> tuple:
        """(the group's host values, how many host arrays THIS caller
        fetched for them: 1 for the claimer, 0 for who found it done)."""
        fetched = 0
        if not self._done.is_set() and self._claimed.acquire(blocking=False):
            # first waiter: the one real d2h sync, not under any lock
            fetched = 1
            try:
                self._host = self._engine.fetch(self._out, self._cq)
                self._out = self._cq = None
            except Exception as e:  # noqa: BLE001 — published to waiters
                self._exc = e
            finally:
                # set even when a BaseException (KeyboardInterrupt)
                # aborts the claimer: waiters must never park forever.
                # The interrupt itself propagates on the claimer's
                # thread only — republishing it to every member would
                # turn one operator Ctrl-C into N failed queries
                self._done.set()
        else:
            self._done.wait()
        if self._exc is not None:
            raise self._exc
        if self._host is None:
            # claimer died without publishing (interpreter-control
            # exception mid-fetch): RuntimeError is device-fault-shaped,
            # so each member's drain resubmits on the host path
            raise RuntimeError("fused d2h fetch aborted before publishing")
        return self._host, fetched


class _FusedSlice:
    """One member query's view of a _FusedOut: its row of the group's
    one output array, in the solo fetch's form."""

    __slots__ = ("_shared", "_qi")

    def __init__(self, shared, qi):
        self._shared = shared
        self._qi = qi

    def fetch(self) -> tuple:
        """((count, inspected, scores, idx[, agg]), host arrays this
        call fetched): the member's row of every per-query part (the
        ?agg= counts demux like scores), views of the group's array."""
        (counts, inspected, *rows), fetched = self._shared.host()
        qi = self._qi
        return (int(counts[qi]), inspected,
                *(r[qi] for r in rows)), fetched


class QueryCoalescer:
    """Cross-request query coalescing: concurrent searches whose next
    dispatch targets the SAME staged BlockBatch stack their compiled
    queries along a query axis and execute as ONE fused
    batch_scan_kernel launch — continuous batching for scans. N
    tenants' dashboards over the same device-resident columns then cost
    ~1 dispatch per coalescing window instead of N.

    Mechanics:
    - submit() parks the query in a per-batch pending group and arms a
      window timer (`window_s`, a few ms). The flush NEVER waits for
      more peers — it fires on the timer or when `max_queries` stack up,
      so a lone query is delayed by at most the window.
    - A dispatch with no potential peer skips the window entirely (the
      `peers` hint on submit, per-BATCH, not merely per-process): serial
      latency is unchanged, and a single request's own sharded
      sub-requests — which target disjoint batches and can never fuse —
      don't tax each other either. The window is only paid when another
      in-flight search could actually share this batch's dispatch.
    - Single-query flushes launch without a query axis (scan_async) so
      they reuse its already-compiled executables.
    - Query tables pad (Q, T, R, top_k) to power-of-two buckets
      (multiblock.stack_queries), so the jit cache keys on predicate
      SHAPE, never predicate values — different tag-sets share one
      compiled executable.
    """

    def __init__(self, engine: MultiBlockEngine, window_s: float = 0.003,
                 max_queries: int = 8, active_fn=None):
        self.engine = engine
        self.window_s = window_s
        self.max_queries = max(2, max_queries)
        # how many searches are in flight right now; <=1 → flush
        # immediately (no peer exists to wait for)
        self._active_fn = active_fn or (lambda: 2)
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        # pending-group key: (id(batch), None) for legacy queries, the
        # stack_group_key tuple (id(batch), plan) for structural ones —
        # same-plan structural peers share a group, different plans
        # wait out disjoint windows and flush solo
        self._pending: dict[tuple, _PendingCoalesce] = {}
        # window deadlines served by ONE long-lived scheduler thread
        # (lazily started): a threading.Timer per armed window would
        # create an OS thread per batch per window on the serving hot
        # path — pure churn at thousands of windows/sec. Heap entries
        # carry gen SECOND so equal deadlines tie-break on the unique
        # int and group keys (which hold plan tuples) never compare.
        self._deadlines: list[tuple[float, int, tuple]] = []  # (t, gen, key)
        self._sched: threading.Thread | None = None
        self._flush_pool = None  # lazily built with the scheduler
        self._gen = 0
        self.dispatches = 0   # fused + solo kernel launches issued here
        self.fused = 0        # launches that served >1 query
        self.queries = 0      # queries served
        self.structural_queries = 0  # structural queries served here
        self.structural_stacked = 0  # ...that shared a fused dispatch
        self.structural_bucketed = 0  # ...whose fused group mixed plans
        # per-bucket occupancy (/debug/scan): str(bucket descriptor) ->
        # {queries, dispatches, active_nodes, slot_nodes} — over-padded
        # buckets show up as a low active/slot ratio
        self._bucket_stats: dict[str, dict] = {}

    def submit(self, batch, mq, top_k: int, peers: int | None = None):
        """Queue one compiled query against `batch`; returns a Future
        resolving to what the drain fetches: a solo flush's one output
        array, as a direct dispatch hands it over, or the member's
        _FusedSlice of a fused launch's. `peers`
        is the caller's count of in-flight searches that could target
        THIS batch (self included); <=1 flushes immediately.

        Structural queries group by PLAN SHAPE (stack_group_key): with
        search_structural_stack_enabled, same-plan concurrent queries
        stack along the fused query axis like any other coalesced
        member; with it off (or for a plan no peer shares) they flush
        solo, and the stack_events counter says which.

        The submitter's active QueryStats is captured WITH the item
        (the contextvar does not survive into the window-timer flush
        thread): at flush time the dispatch's profiled stage times are
        apportioned across the member queries' stats. So is, while a
        tracer is installed, the submitter's span context (its
        `batcher.Search`): the member's `coalescer.wait` hangs under it
        whichever thread flushes."""
        import concurrent.futures
        import heapq
        import time as _time

        fut = concurrent.futures.Future()
        parent = None
        if tracing.get_tracer() is not None:
            parent = tracing.current_span().context
        st = getattr(mq, "structural", None)
        key = (id(batch), None)
        if st is not None:
            skey = None
            if _structural.STRUCTURAL.stack_enabled:
                skey = _structural.STRUCTURAL.stack_group_key(batch, st)
            if skey is None:
                # stacking disabled: dispatch solo NOW (the pre-stacking
                # behavior — the solo flush reuses this plan's compiled
                # executable). gen=-1 marks the metric as already
                # recorded here, so _run won't double-book solo_shape.
                obs.structural_stack_events.inc(result="solo_disabled")
                grp = _PendingCoalesce(batch, -1)
                grp.items.append((mq, top_k, fut, tracing.now_ns(),
                                  query_stats.current(), parent))
                self._run(grp)
                return fut
            key = skey
        if getattr(mq, "agg_stage", None) is not None:
            # ?agg= members group apart from plain peers: the agg static
            # changes the fused kernel's jit key, and a mixed group
            # would make the no-agg hot path's compiled shape depend on
            # whichever member happened to join the window
            key = key + ("agg",)
        if mq.val_hits is not None:
            # a member that brings a hit mask groups apart from those
            # that bring ranges only: one mask in a fused launch gives
            # every member a [G, T, Vmax] row and a gather for every
            # slot of every entry (0.73 s a member and term for 4,096
            # pages on a v5e, against milliseconds of compares)
            key = key + ("mask",)
        elif mq.val_ranges.shape[2] > WIDE_RANGES:
            # and so do members of many ranges a term: a fused launch
            # pads every member to its widest, and at 512 ranges the
            # compares cost 3.6 ms a member and term where at 64 they
            # cost 0.7 (61 and under 9 when PR 33 set this key; since
            # PR 36 they run once an entry: PERF.md section 6)
            key = key + ("wide",)
        flush_now = None
        with self._lock:
            grp = self._pending.get(key)
            if grp is None:
                self._gen += 1
                grp = self._pending[key] = _PendingCoalesce(batch, self._gen)
            grp.items.append((mq, top_k, fut, tracing.now_ns(),
                              query_stats.current(), parent))
            if len(grp.items) >= self.max_queries:
                del self._pending[key]
                flush_now = grp
            elif len(grp.items) == 1:
                hint = peers if peers is not None else self._active_fn()
                if hint <= 1:
                    # no peer can share this batch's dispatch: a window
                    # would be pure added latency
                    del self._pending[key]
                    flush_now = grp
                else:
                    heapq.heappush(
                        self._deadlines,
                        (_time.perf_counter() + self.window_s, grp.gen,
                         key))
                    if self._sched is None:
                        self._flush_pool = \
                            concurrent.futures.ThreadPoolExecutor(
                                max_workers=4,
                                thread_name_prefix="coalesce-flush")
                        self._sched = threading.Thread(
                            target=self._window_loop, daemon=True,
                            name="coalesce-window")
                        self._sched.start()
                    self._cv.notify()
            # queue-depth gauge AFTER the flush-now removal above: only
            # queries actually parked in a window count as pending
            obs.coalesce_pending.set(
                sum(len(g.items) for g in self._pending.values()))
        if flush_now is not None:
            self._run(flush_now)
        return fut

    def _window_loop(self) -> None:
        """Single scheduler thread draining window deadlines. Stale
        entries (groups a size-triggered flush already took) are skipped
        by the gen check — nothing is ever cancelled out of the heap.
        Due flushes are HANDED OFF to a small pool: _run stages, uploads
        and may jit-compile a first-seen kernel shape, and running that
        inline would head-of-line-block every other batch's window
        behind one slow group."""
        import heapq
        import time as _time

        while True:
            grp = None
            with self._cv:
                while not self._deadlines:
                    self._cv.wait()
                deadline, gen, key = self._deadlines[0]
                wait = deadline - _time.perf_counter()
                if wait > 0:
                    self._cv.wait(wait)
                    continue
                heapq.heappop(self._deadlines)
                pend = self._pending.get(key)
                if pend is None or pend.gen != gen:
                    continue  # size-triggered flush beat the window
                del self._pending[key]
                obs.coalesce_pending.set(
                    sum(len(g.items) for g in self._pending.values()))
                grp = pend
            self._flush_pool.submit(self._run, grp)

    @staticmethod
    def _attribute(items, recs, wall_s: float) -> None:
        """Apportion one (possibly fused) dispatch's cost across the
        member queries' stats by their padded predicate-table rows,
        CONSERVING the totals: per stage, the attributed shares sum to
        the dispatch total exactly (query_stats.apportion gives the
        last member the float remainder). With profiling disabled there
        are no records; the measured wall books as "execute" so the
        per-tenant device-seconds bill degrades to wall-clock rather
        than to zero."""
        stats = [it[4] for it in items]
        if all(s is None for s in stats):
            return
        totals: dict[str, float] = {}
        h2d = 0
        for rd in recs:
            for k, v in (rd.get("stages_ms") or {}).items():
                totals[k] = totals.get(k, 0.0) + v / 1e3
            h2d += rd.get("h2d_bytes", 0)
        if not totals:
            totals = {"execute": wall_s}

        def table_rows(mq) -> int:
            # stacked structural members weigh their plan's parameter
            # tables alongside the legacy term tables — a member whose
            # probe masks dominated the fused kernel's reads gets the
            # proportional share (conservation via apportion as before).
            # st is each member's OWN CompiledStructural, so under
            # shape-bucketed stacking the weight counts the member's
            # ACTIVE node tables, never the bucket's pad slots
            w = max(1, int(mq.term_keys.size))
            st = getattr(mq, "structural", None)
            if st is not None:
                w += st.weight()
            return w

        weights = [table_rows(it[0]) for it in items]
        shares = query_stats.apportion(totals, weights)
        byte_shares = query_stats.apportion({"b": float(h2d)}, weights)
        for qs, share, bs in zip(stats, shares, byte_shares):
            if qs is not None:
                qs.add_device_stages(share, h2d_bytes=bs["b"],
                                     fused_q=len(items))

    def _trace_launch(self, lspan, items, batch, out, recs,
                      launched: int, cpu_launched: int | None) -> None:
        """Close one launch's spans at `launched`, the stamp taken when
        the kernel call returned (`cpu_launched` the flushing thread's
        CPU clock beside it): `coalescer.launch` (open since the
        flush began) ends there, the device timeline takes the outputs
        over, and every traced member gets its `coalescer.wait`, from
        its own submit to this launch, under its own `batcher.Search`.
        All carry the launch id, so a reader joins a member's wait to
        the one launch and the one `device.scan` that served it."""
        if not lspan.recording:
            return
        # the profiler's names: a fused launch is kernel and mode
        # `coalesced`, a solo one kernel `multi` in mode `batched`
        fused = len(items) > 1
        kernel = "coalesced" if fused else "multi"
        mode = "coalesced" if fused else "batched"
        launch = profile.DEVICE_TIMELINE.watch(
            out, lspan.context, len(items), len(batch.blocks), kernel)
        blocks = len(batch.blocks)
        bucket = block_bucket(blocks)
        if bucket > blocks:
            # as on `dispatch.execute`: only where there are pad rows
            lspan.set_attribute("blocks_bucket", bucket)
        lspan.set_attributes(
            launch=launch, queries=len(items), blocks=blocks,
            kernel=kernel, shards=self.engine.n_shards,
            pages_per_shard=self.engine.pages_per_shard(batch),
            jit_cache=(recs[0].get("jit_cache", "") if recs else ""))
        lspan.end(launched, cpu_launched)
        for _mq, _k, fut, t_submit, _qs, parent in items:
            # the member's drain names the launch it slept on
            # (`batcher.await_launch`)
            fut.launch = launch
            if parent is not None:
                tracing.record_span(
                    "coalescer.wait", t_submit, launched, parent=parent,
                    launch=launch, queries=len(items), mode=mode)

    def _run(self, grp: _PendingCoalesce) -> None:
        items = grp.items
        try:
            now = tracing.now_ns()
            for _mq, _k, _fut, t0, _qs, _p in items:
                obs.coalesce_wait_seconds.observe((now - t0) / 1e9)
            # the launch's own span hangs under its first traced member
            # and is CURRENT for the kernel call, so the profiler's
            # `dispatch.<stage>` spans land under it on whichever thread
            # flushes (the window pool's threads carry no span)
            first = next((p for *_r, p in items
                          if p is not None and p.sampled), None)
            lspan = tracing.NOOP_SPAN
            if first is not None and tracing.get_tracer() is not None:
                lspan = tracing.start_span(
                    "coalescer.launch", parent=first, start_ns=now,
                    cpu_start_ns=tracing.cpu_ns())
            structural = bool(
                items and getattr(items[0][0], "structural", None)
                is not None)
            # a fused structural group whose member plans DIFFER fused
            # through the bucket canonicalization (bucket_group_key) —
            # booked separately so mixed-traffic fusion is observable
            bucketed = structural and len(items) > 1 and any(
                getattr(it[0], "structural").plan
                != items[0][0].structural.plan for it in items[1:])
            with self._lock:  # _run races: window thread vs size flush
                self.dispatches += 1
                self.queries += len(items)
                if len(items) > 1:
                    self.fused += 1
                if structural:
                    self.structural_queries += len(items)
                    if len(items) > 1:
                        self.structural_stacked += len(items)
                    if bucketed:
                        self.structural_bucketed += len(items)
            if structural and grp.gen >= 0:
                # gen=-1 groups booked solo_disabled at submit; here a
                # fused flush books every member as stacked (bucketed
                # when plans differ) and a lone member as solo_shape —
                # unstackable (peerless) plan shapes are visible, never
                # a silent solo flush
                if bucketed:
                    obs.structural_stack_events.inc(
                        len(items), result="stacked_bucketed")
                elif len(items) > 1:
                    obs.structural_stack_events.inc(len(items),
                                                    result="stacked")
                else:
                    obs.structural_stack_events.inc(result="solo_shape")
            if len(items) == 1:
                mq, _k, fut, _t0, _qs, _p = items[0]
                with lspan:
                    t0d = tracing.now_ns()
                    with profile.collect_records() as recs:
                        out = self.engine.scan_async(grp.batch, mq)
                    launched = tracing.now_ns()
                    self._trace_launch(
                        lspan, items, grp.batch, out, recs, launched,
                        tracing.cpu_ns() if lspan.recording else None)
                self._attribute(items, recs, (launched - t0d) / 1e9)
                start_fetch(out)
                obs.scan_dispatches.inc(mode="batched",
                                        shards=self.engine.n_shards)
                fut.set_result(out)
                return
            mqs = [it[0] for it in items]
            cq = stack_queries(mqs)
            st = getattr(cq, "structural", None)
            if st is not None and getattr(st, "slot_nodes", 0):
                # bucket occupancy: active (real) vs slot (padded)
                # nodes per bucket descriptor — /debug/scan surfaces
                # over-padded buckets
                bkey = str(st.plan)
                with self._lock:
                    row = self._bucket_stats.setdefault(
                        bkey, {"queries": 0, "dispatches": 0,
                               "active_nodes": 0, "slot_nodes": 0})
                    row["queries"] += st.n_queries
                    row["dispatches"] += 1
                    row["active_nodes"] += st.active_nodes
                    row["slot_nodes"] += st.slot_nodes
            k = max(it[1] for it in items)
            with lspan:
                t0d = tracing.now_ns()
                with profile.collect_records() as recs:
                    out = self.engine.coalesced_scan_async(grp.batch, cq, k)
                launched = tracing.now_ns()
                self._trace_launch(
                    lspan, items, grp.batch, out, recs, launched,
                    tracing.cpu_ns() if lspan.recording else None)
            self._attribute(items, recs, (launched - t0d) / 1e9)
            obs.scan_dispatches.inc(mode="coalesced",
                                    shards=self.engine.n_shards)
            obs.coalesced_queries.inc(len(items))
            # D2H starts async NOW; the one blocking sync point happens
            # on the first waiter's drain (lazy demux), not here — a
            # size-triggered flush runs on the last submitter's thread,
            # which still has its own dispatch loop to overlap
            start_fetch(out)
            shared = _FusedOut(out, self.engine, cq)
            for qi, it in enumerate(items):
                it[2].set_result(_FusedSlice(shared, qi))
        except BaseException as e:  # noqa: BLE001 — delivered via futures
            for it in items:
                if not it[2].done():
                    it[2].set_exception(e)

    def stats(self) -> dict:
        with self._lock:
            pending = sum(len(g.items) for g in self._pending.values())
            bucket_rows = {bk: dict(row)
                           for bk, row in self._bucket_stats.items()}
        return {
            "dispatches": self.dispatches,
            "fused_dispatches": self.fused,
            "queries": self.queries,
            "ratio": round(self.queries / max(1, self.dispatches), 3),
            "pending": pending,
            "window_ms": self.window_s * 1e3,
            # plan-shape stacking visibility (/debug/scan): how many
            # structural queries came through and what share of them
            # actually shared a fused dispatch
            "structural_queries": self.structural_queries,
            "structural_stacked": self.structural_stacked,
            "structural_stack_ratio": round(
                self.structural_stacked
                / max(1, self.structural_queries), 3),
            # shape-bucketed fusion visibility: mixed-plan queries that
            # shared a dispatch, plus per-bucket stack ratios and node
            # occupancy (active = real slots, the rest is bucket pad)
            "structural_bucketed": self.structural_bucketed,
            "buckets": {
                bk: {
                    "queries": row["queries"],
                    "dispatches": row["dispatches"],
                    "stack_ratio": round(
                        row["queries"] / max(1, row["dispatches"]), 3),
                    "occupancy": round(
                        row["active_nodes"]
                        / max(1, row["slot_nodes"]), 3),
                }
                for bk, row in bucket_rows.items()
            },
        }


class BlockBatcher:
    """Groups ScanJobs into staged device batches and runs searches over
    them. Thread-safe; one instance per TempoDB."""

    def __init__(self, mesh=None, top_k: int = DEFAULT_TOP_K,
                 max_batch_pages: int = 4096,
                 cache_bytes: int = 4 << 30,
                 host_cache_bytes: int | None = None,
                 pipeline_depth: int = 2,
                 io_workers: int = 8,
                 coalesce_window_s: float = 0.003,
                 coalesce_max_queries: int = 8,
                 device_probe_min_vals: int | None = None):
        self.engine = MultiBlockEngine(
            top_k=top_k, mesh=mesh,
            device_probe_min_vals=device_probe_min_vals)
        self.max_batch_pages = max_batch_pages
        self.cache_bytes = cache_bytes
        if host_cache_bytes is None:
            # auto-size: the host tier retains stacked batches (and pins
            # their source pages), so an unconditional 32 GB default
            # OOM-kills small hosts — cap at half of physical RAM
            import os
            try:
                phys = os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
            except (ValueError, OSError, AttributeError):
                phys = 16 << 30
            host_cache_bytes = min(32 << 30, phys // 2)
        self.host_cache_bytes = host_cache_bytes
        self.pipeline_depth = max(1, pipeline_depth)
        self.io_workers = io_workers
        self._cache: OrderedDict[tuple, _CachedBatch] = OrderedDict()
        self._cache_total = 0
        self._cache_peak = 0        # high water of _cache_total, as published
        self._probe_dict_total = 0  # staged-dict bytes across _cache
        self._span_total = 0        # span-column bytes across _cache
        # logical (unpacked-layout) bytes across both tiers — the other
        # half of the packed-residency accounting split: budgets charge
        # PHYSICAL bytes (that is why packing fits more blocks), the
        # logical gauges say how much unpacked data those bytes carry
        self._cache_logical = 0
        self._host_logical = 0
        # host-RAM tier between the object store and HBM: stacked numpy
        # batches, byte-budgeted separately. An HBM eviction leaves the
        # host copy, so re-staging an evicted batch is one H2D copy, not
        # IO + decompress + restack (VERDICT r3 #2)
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
        self._warmed_shapes: set = set()  # compile-warm dedupe
        self._prune_cache: OrderedDict = OrderedDict()
        self._plan_cache: OrderedDict = OrderedDict()
        self._lock = threading.Lock()
        # staging lookahead: stages a search's next missing group while
        # the groups it takes are scanned, overlapping H2D with compute
        # (double-buffering). More than one thread so CONCURRENT
        # searches' lookaheads don't serialize behind each other (each
        # search still submits one at a time; _staged dedupes racing
        # stages)
        import concurrent.futures
        self._prefetcher = concurrent.futures.ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="stage-prefetch")
        # cross-request query coalescing: concurrent searches' dispatches
        # over the same staged batch fuse into one multi-query kernel
        # launch. coalesce_max_queries <= 1 disables (every submit
        # dispatches directly, the pre-coalescer behavior).
        # _interest counts, per batch gkey, how many in-flight searches
        # plan to scan it; _unplanned counts searches that entered but
        # haven't resolved their plan yet (unknown targets — they could
        # hit any batch, so they count as potential peers everywhere).
        # The coalescing window is armed only when interest+unplanned
        # says a same-batch peer can actually arrive: a single request's
        # sharded sub-requests cover DISJOINT batches and must not tax
        # each other a window apiece
        self._interest: dict[tuple, int] = {}
        self._unplanned = 0
        self.coalescer = None
        if coalesce_max_queries > 1:
            self.coalescer = QueryCoalescer(
                self.engine, window_s=coalesce_window_s,
                max_queries=coalesce_max_queries)
        self.last_dispatches = 0  # diagnostics: dispatch SUBMITS in last
        # search — under coalescing several searches can share one kernel
        # launch, so the global launch count lives in the
        # scan_dispatches{mode=batched|coalesced} counters instead
        self.last_scan = None     # /debug/scan: last search's breakdown

    # ------------------------------------------------------------------
    # planning

    def group_cap(self) -> int:
        """Pages a group may hold: `max_batch_pages` for each device
        that reads it. A mesh splits a group's page axis over its
        devices, so a group of this size costs every device the launch
        a one-chip engine makes at `max_batch_pages`, for the one fixed
        host price of a launch. Read when a plan is made, never before:
        the mesh is attached after the batcher is built
        (`TempoDB._ensure_mesh`)."""
        return self.max_batch_pages * self.engine.n_shards

    @staticmethod
    def _cuts(j: ScanJob, cap: int) -> bool:
        """Content-defined group boundary: depends ONLY on this job's key
        and size (and the group cap), never on neighbors, so group
        composition is a local property. Cut probability 1/divisor
        makes the expected group ~cap/2, leaving headroom so churn
        rarely propagates through the hard page cap to the next anchor.
        plan() additionally guards cuts behind a min group size (cap/4,
        the CDC min-chunk-size trick) so groups never fragment below
        batching efficiency. Where a job's pages divide the cap (64
        into 4,096) the divisor grows with the mesh by whole multiples,
        so a mesh's anchors are a subset of one chip's."""
        import zlib

        divisor = max(2, cap // (2 * max(1, j.n_pages)))
        return zlib.crc32(repr(j.key).encode()) % divisor == 0

    def plan(self, jobs: list[ScanJob]) -> list[list[ScanJob]]:
        cap = self.group_cap()
        min_pages = cap // 4
        buckets: dict[tuple, list[ScanJob]] = {}
        for j in sorted(jobs, key=lambda j: (
                j.header.get("min_start_s") or 0, j.key)):
            buckets.setdefault(j.geometry, []).append(j)
        groups = []
        for _geo, js in sorted(buckets.items()):
            cur: list[ScanJob] = []
            cur_pages = 0
            for j in js:
                if cur and (cur_pages + j.n_pages > cap
                            or (cur_pages >= min_pages
                                and self._cuts(j, cap))):
                    groups.append(cur)
                    cur, cur_pages = [], 0
                cur.append(j)
                cur_pages += j.n_pages
            if cur:
                groups.append(cur)
        return groups

    # ------------------------------------------------------------------
    # staging cache

    @staticmethod
    def _dict_bytes(batch) -> int:
        """HBM held by a batch's staged device-probe dictionaries."""
        return sum(int(d.nbytes)
                   for d in getattr(batch, "staged_dicts", {}).values())

    @staticmethod
    def _span_bytes(batch) -> int:
        """HBM held by a batch's structural span columns."""
        return _structural.span_device_bytes(
            getattr(batch, "span_device", None))

    def _publish_gauges_locked(self) -> None:
        """Occupancy gauges for /metrics (caller holds self._lock): HBM
        + host tier bytes, and the HBM share held by staged device-probe
        dictionaries across resident batches. All three are running
        totals (the _cache_total idiom) — this must stay O(1), it runs
        on every stage/evict under the global lock."""
        obs.hbm_cache_bytes.set(self._cache_total)
        if self._cache_total > self._cache_peak:
            # what the gauge above ever showed: a scrape at the ends of
            # an interval cannot see an overshoot inside it
            self._cache_peak = self._cache_total
            obs.hbm_cache_peak_bytes.set(self._cache_peak)
        obs.host_cache_bytes.set(self._host_total)
        obs.probe_dict_bytes.set(self._probe_dict_total)
        obs.structural_span_bytes.set(self._span_total)
        obs.hbm_logical_bytes.set(self._cache_logical)
        obs.host_logical_bytes.set(self._host_logical)

    def _evict_host_locked(self) -> None:
        """LRU-evict host-tier batches until the budget holds — caller
        holds self._lock. An entry's charge is its nbytes plus any
        CPU-pinned fallback copies host_scan memoized on it."""
        while (self._host_total > self.host_cache_bytes
               and len(self._host_cache) > 1):
            k, oldh = self._host_cache.popitem(last=False)
            self._host_total -= oldh.nbytes
            self._host_logical -= oldh.logical_nbytes
            self._host_total -= self._cpu_staged_bytes.pop(k, 0)
            obs.batch_cache_events.inc(result="host_evict")

    def _keep_memo_locked(self, gkey: tuple, old: _CachedBatch) -> None:
        """An evicted batch's prepare memo outlives it on the host-tier
        entry — caller holds self._lock. The memo is host work (the
        per-block predicate compile), and a tenant larger than its HBM
        budget would pay it again at every re-stage: on a v5e that was
        39 % of all lookups and the largest span of a search. What
        holds device state stays behind: a predicate's uploaded tables
        (HBM the eviction just gave back; the next dispatch uploads
        them again), and whole entries compiled against the batch's
        staged dictionaries or a structural plan."""
        host = self._host_cache.get(gkey)
        if host is None:
            return
        host.query_memo = OrderedDict(
            (sig, {k: v for k, v in pre.items()
                   if k not in ("device_params", "device_params_bytes")})
            for sig, pre in old.query_cache.items()
            if pre.get("val_hits") is None and pre.get("structural") is None)

    def _drop_hbm_locked(self, gkey: tuple) -> None:
        """Remove one staged batch and release its budget charge —
        caller holds self._lock. The single eviction primitive shared by
        the LRU, the ownership rebalance, and the deferred-at-unpin
        sweep, so the accounting subtraction happens in exactly one
        place."""
        old = self._cache.pop(gkey, None)
        if old is None:
            return
        self._keep_memo_locked(gkey, old)
        self._cache_total -= old.nbytes
        self._cache_logical -= old.logical
        self._probe_dict_total -= self._dict_bytes(old.batch)
        self._span_total -= self._span_bytes(old.batch)
        MASK_BYTES.add("memo", -old.mask_bytes)
        obs.batch_cache_events.inc(result="evict")
        obs.hbm_evicted_bytes.inc(old.nbytes)

    def _evict_hbm_locked(self) -> None:
        """LRU-evict staged batches until the HBM budget holds — caller
        holds self._lock. Pinned entries (actively scanned by some
        search) are skipped: evicting them reclaims nothing (the
        in-flight dispatch pins the device arrays) and guarantees an
        immediate re-stage."""
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
        bytes. Caller holds self._lock."""
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
        with self._lock:
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

    def ownership_residency(self) -> list:
        """Per-resident-batch ownership view for /debug/ownership: which
        placement group each staged batch anchors to, who owns it, and
        whether a deferred rebalance eviction is pending on it."""
        with self._lock:
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

    def _unpin_locked(self, entries) -> None:
        """Give back pins taken by `_staged(pin=True)` — caller holds
        self._lock. What the pins held over budget goes now: first the
        ownership-rebalance deferrals (exactly-once, identity-checked),
        then ordinary LRU pressure."""
        for c in entries:
            c.pins -= 1
        self._run_deferred_evictions_locked()
        self._evict_hbm_locked()

    def _unpin_unused(self, fut) -> None:
        """Done-callback of a look-ahead no search came back for."""
        if fut.exception() is None:
            entry = fut.result()     # done: returns at once
            with self._lock:
                self._unpin_locked((entry,))

    def _resident_locked(self, key: tuple, pin: bool):
        """The group's resident entry, touched, counted as a hit and,
        with `pin`, pinned; None where the group is not resident —
        caller holds self._lock."""
        hit = self._cache.get(key)
        if hit is not None:
            self._cache.move_to_end(key)
            obs.batch_cache_events.inc(result="hit")
            if pin:
                hit.pins += 1
        return hit

    def _staged(self, group: list[ScanJob], pin: bool = False,
                parent=None) -> _CachedBatch:
        """The group's staged batch, from the HBM cache or staged now.
        `pin` takes a pin under the same lock that finds or inserts the
        entry, so no eviction pass can drop what the caller is about to
        scan (its own insert's included); the caller gives it back
        through `_unpin_locked`. `parent` is the span context a
        look-ahead thread writes `batcher.place` under (a stage on the
        searching thread finds its `batcher.Search` current)."""
        key = tuple(j.key for j in group)
        while True:
            with self._lock:
                hit = self._resident_locked(key, pin)
                if hit is not None:
                    return hit
                ev = self._staging.get(key)
                if ev is None:
                    # we are the stager for this key
                    ev = self._staging[key] = threading.Event()
                    break
            # another thread is staging this exact group: wait for it
            # rather than duplicating the IO+decompress+H2D (and
            # transiently doubling HBM for the batch)
            ev.wait()
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
            nbytes = int(batch.nbytes)
            entry = _CachedBatch(batch=batch, nbytes=nbytes,
                                 logical=int(batch.logical_nbytes),
                                 jobs=list(group), pins=int(pin))
            with self._lock:
                obs.batch_cache_events.inc(result="miss")
                # what the last eviction of this group kept of its memo
                if host.query_memo is not None:
                    entry.query_cache, host.query_memo = (
                        host.query_memo, None)
                prev = self._cache.pop(key, None)
                if prev is not None:
                    self._cache_total -= prev.nbytes
                    self._cache_logical -= prev.logical
                    self._probe_dict_total -= self._dict_bytes(prev.batch)
                    self._span_total -= self._span_bytes(prev.batch)
                    MASK_BYTES.add("memo", -prev.mask_bytes)
                self._cache[key] = entry
                self._cache_total += nbytes
                self._cache_logical += entry.logical
                self._probe_dict_total += self._dict_bytes(batch)
                self._span_total += self._span_bytes(batch)
                self._evict_hbm_locked()
            return entry
        finally:
            with self._lock:
                self._staging.pop(key, None)
            ev.set()

    def _load_host(self, key: tuple, group: list[ScanJob]):
        """Host-tier staging (IO + decompress + stack, NO device put):
        the first half of _staged, and the WHOLE staging for the
        breaker's host-fallback route."""
        with self._lock:
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
            with self._lock:
                self._host_cache[key] = host
                self._host_total += host.nbytes
                self._host_logical += host.logical_nbytes
                self._evict_host_locked()
                self._publish_gauges_locked()
            obs.batch_cache_events.inc(result="host_miss")
        else:
            obs.batch_cache_events.inc(result="host_hit")
        return host

    def _host_batch(self, group: list[ScanJob]):
        """The host-fallback route's staging: host tier only, deduped
        against concurrent fallers the same way _staged dedupes device
        staging (a distinct event key — a host-route stage must not
        block behind a device stage wedging on the same group)."""
        key = tuple(j.key for j in group)
        ev_key = ("host",) + key
        while True:
            with self._lock:
                if key in self._host_cache:
                    we_stage = False
                    break
                ev = self._staging.get(ev_key)
                if ev is None:
                    ev = self._staging[ev_key] = threading.Event()
                    we_stage = True
                    break
            ev.wait()
        if not we_stage:
            return self._load_host(key, group)  # resident: hit counters
        try:
            return self._load_host(key, group)
        finally:
            with self._lock:
                self._staging.pop(ev_key, None)
            ev.set()

    def invalidate(self, live_block_ids: set[str]) -> None:
        """Drop cached batches containing blocks no longer in the
        blocklist (called from the poll loop) — both HBM and host tiers."""
        with self._lock:
            dead = [k for k in self._cache
                    if any(jk[0] not in live_block_ids for jk in k)]
            for k in dead:
                old = self._cache.pop(k)
                self._cache_total -= old.nbytes
                self._cache_logical -= old.logical
                self._probe_dict_total -= self._dict_bytes(old.batch)
                self._span_total -= self._span_bytes(old.batch)
                MASK_BYTES.add("memo", -old.mask_bytes)
                # a pending rebalance deferral for a dead block's batch
                # is satisfied by this removal — keeping the marker
                # would double-evict whatever re-stages under the key
                self._evict_deferred.pop(k, None)
            dead_h = [k for k in self._host_cache
                      if any(jk[0] not in live_block_ids for jk in k)]
            for k in dead_h:
                oldh = self._host_cache.pop(k)
                self._host_total -= oldh.nbytes
                self._host_logical -= oldh.logical_nbytes
                self._host_total -= self._cpu_staged_bytes.pop(k, 0)
            self._publish_gauges_locked()

    def prewarm(self, groups: list[list[ScanJob]],
                warm_compile: bool = True,
                stop: threading.Event | None = None) -> int:
        """Stage groups ahead of queries (called in the background after
        a poll): fills the host tier + HBM up to their budgets in plan
        order, and optionally warms the XLA compile cache for the
        staged shapes with a throwaway dispatch, so the first real query
        pays neither staging nor compile. Returns groups staged."""
        staged = 0
        budget = self.cache_bytes
        for group in groups:
            if stop is not None and stop.is_set():
                break
            if budget <= 0:
                break
            gkey = tuple(j.key for j in group)
            if OWNERSHIP.enabled:
                if not OWNERSHIP.owns_group(gkey):
                    # non-owned groups serve through the host route —
                    # prewarming them would stage exactly the duplicate
                    # HBM copy ownership exists to avoid
                    continue
            with self._lock:
                resident = gkey in self._cache
            try:
                cached = self._staged(group)
            except Exception:  # noqa: BLE001 — prewarm is best-effort
                continue
            # only actual staging WORK spends the budget: charging
            # resident hits would exhaust it on the warm prefix every
            # poll and never reach newly added groups (code-review r4)
            if not resident:
                budget -= cached.nbytes
                staged += 1
            if stop is not None and stop.is_set():
                break
            if warm_compile:
                try:
                    self._warm_compile(cached)
                except Exception:  # noqa: BLE001 — best-effort
                    pass
        return staged

    def _warm_compile(self, cached: _CachedBatch) -> None:
        """Throwaway dispatches to populate the jit cache for this
        batch's shape at the common term counts (0 = duration/window
        only, 2 = the typical tag AND). The jit cache keys on the PADDED
        shape (pow2-bucketed) — warming is deduped per shape signature,
        or a 100-group tenant would device-scan the whole corpus ~200x
        for ~log2 distinct compiles (code-review r4)."""
        import numpy as np

        from .multiblock import MultiQuery

        # dtypes are part of the jit cache key too: dictionary-size
        # narrowing means two same-shaped batches can carry int8 vs
        # int16 kv columns and compile separately (code-review r5);
        # the packed-residency width descriptor likewise
        shape_sig = (cached.batch.device["entry_valid"].shape,
                     cached.batch.device["kv_key"].shape,
                     str(cached.batch.device["kv_key"].dtype),
                     str(cached.batch.device["kv_val"].dtype),
                     cached.batch.widths,
                     block_bucket(len(cached.batch.blocks)))
        with self._lock:
            if shape_sig in self._warmed_shapes:
                return
            self._warmed_shapes.add(shape_sig)
        B = block_bucket(len(cached.batch.blocks))
        for n_terms in (0, 2):
            mq = MultiQuery(
                term_keys=np.full((B, max(1, n_terms)), -1, dtype=np.int32),
                val_ranges=np.tile(np.array([1, 0], dtype=np.int32),
                                   (B, max(1, n_terms), 1, 1)),
                dur_lo=1, dur_hi=0,  # empty range: matches nothing
                win_start=1, win_end=0,
                limit=20, n_terms=n_terms)
            self.engine.scan(cached.batch, mq)

    # ------------------------------------------------------------------
    # search

    def search(self, jobs: list[ScanJob], req,
               results: SearchResults | None = None,
               plan_key=None, groups: list | None = None,
               tenant: str | None = None) -> SearchResults:
        """Run the request over all jobs: group → stage → compile →
        dispatch (pipelined, early-quitting) → merge. `plan_key` (e.g.
        (tenant, blocklist-epoch)) memoizes the grouping — the plan is a
        pure function of the job list, and re-sorting 10K jobs per query
        is measurable host overhead. Callers that already hold the plan
        (tempodb's protocol-path job cache) pass `groups` directly.
        `tenant` is whose jobs these are, for the `batcher.Search` span.

        Concurrent calls coalesce: dispatches landing on the same staged
        batch within the coalescing window fuse into one multi-query
        kernel launch (see QueryCoalescer). A batch is pinned in the HBM
        cache while this search has it in flight (staged ahead, taken,
        dispatched and not yet drained): the cache stands over budget by
        at most `pipeline_depth` + 1 groups for each concurrent search."""
        with self._lock:
            self._unplanned += 1
        pinned: list[_CachedBatch] = []   # pins held now, one per entry
        prefetched: dict = {}        # gkey -> (look-ahead future, event)
        interest: list[tuple] = []   # gkeys registered once planned
        planned = [False]
        try:
            return self._search_impl(jobs, req, results, plan_key, groups,
                                     pinned, prefetched, interest, planned,
                                     tenant)
        finally:
            # an early quit or an exception leaves a look-ahead pending:
            # cancel it so a not-yet-started stage doesn't burn
            # IO+decompress+H2D (and possibly evict a hotter batch) for a
            # group nobody needs; an already-running one completes via
            # _staged's dedupe and gives its pin back when it does
            for f, _ev in prefetched.values():
                if not f.cancel():
                    f.add_done_callback(self._unpin_unused)
            with self._lock:
                if planned[0]:
                    for k in interest:
                        n = self._interest.get(k, 0) - 1
                        if n <= 0:
                            self._interest.pop(k, None)
                        else:
                            self._interest[k] = n
                else:  # died before the plan resolved
                    self._unplanned -= 1
                # whatever an exception or an early quit left in flight
                self._unpin_locked(pinned)

    def _search_impl(self, jobs: list[ScanJob], req,
                     results: SearchResults | None,
                     plan_key, groups: list | None,
                     pinned: list, prefetched: dict, interest: list,
                     planned: list,
                     tenant: str | None = None) -> SearchResults:
        from .pipeline import is_exhaustive

        results = results or SearchResults.for_request(req)
        exhaustive = is_exhaustive(req)
        # the active per-query stats (None when the layer is off): this
        # search's skip reasons, cache events, placement bytes and
        # attributed device time all land here. Read ONCE — every
        # recording site below is behind this None check.
        qs = query_stats.current()
        if groups is None and plan_key is not None:
            # one entry per plan_key[0] (tenant): a stale generation is
            # never hittable again (the epoch only moves forward), so
            # keeping it would just pin 10K dead ScanJobs. The cap is
            # part of the generation: a plan made before the mesh was
            # attached must not outlive it
            tenant_key = plan_key[0]
            gen = (*plan_key[1:], self.group_cap())
            with self._lock:
                hit = self._plan_cache.get(tenant_key)
                if hit is not None and hit[0] == gen:
                    groups = hit[1]
        if groups is None:
            groups = self.plan(jobs)
            if plan_key is not None:
                with self._lock:
                    self._plan_cache[tenant_key] = (gen, groups)
                    while len(self._plan_cache) > 64:
                        self._plan_cache.popitem(last=False)
        # plan is final: declare which batches this search will scan so
        # the coalescer can tell a real same-batch peer from an unrelated
        # concurrent search (which must not make us wait out a window)
        gkeys = [tuple(j.key for j in g) for g in groups]
        with self._lock:
            self._unplanned -= 1
            planned[0] = True
            for k in gkeys:
                self._interest[k] = self._interest.get(k, 0) + 1
            interest.extend(gkeys)
        inflight: deque = deque()
        dispatches = 0
        # per-stage wall time for the LAST search, exposed at /debug/scan
        # (reference pprof/debug role, cmd/tempo/main.go:54-115): the
        # operator's first question about a slow query is which stage ate
        # it — host prune, staging IO+H2D, predicate compile, kernel, or
        # the D2H fetch/merge
        stages = {"header_prune": 0.0, "staging": 0.0, "prepare": 0.0,
                  "dispatch": 0.0, "drain": 0.0, "host_fallback": 0.0}
        t_search0 = tracing.now_ns()

        def book(stage, t0, c0, gi, **attrs):
            """One stage interval ends now. Its seconds go to the stage
            sums; in a traced search the same two stamps make the
            `batcher.<stage>` child of `batcher.Search` (`span`, bound
            below, before any stage runs), and `c0`, this thread's
            `cpu_ns()` beside `t0` (None in an untraced search), says how
            much of the interval it was on a core. `gi` is the group's
            index in the plan, whenever the walk took it."""
            t1 = tracing.now_ns()
            stages[stage] += (t1 - t0) / 1e9
            probes = attrs.pop("probes", None)
            if span.recording:
                c1 = tracing.cpu_ns()
                child = tracing.start_span(
                    "batcher.stage" if stage == "staging"
                    else "batcher." + stage, parent=span.context,
                    start_ns=t0, cpu_start_ns=c0, group=gi,
                    blocks=len(groups[gi]), **attrs)
                if probes is not None:
                    # the compile over the group's distinct dictionaries,
                    # inside `batcher.prepare` (compile_multi's stamps)
                    p0, p1, probed = probes
                    tracing.record_span("dict_probe.probe", p0, p1,
                                        parent=child.context,
                                        **probe_summary(probed))
                child.end(t1, c1)

        def release(cached):
            """This search is done with `cached`: its pin goes, and with
            it whatever the pin held over budget."""
            pinned.remove(cached)
            with self._lock:
                self._unpin_locked((cached,))

        def drain_one():
            t0 = tracing.now_ns()
            item = inflight.popleft()
            dspan = tracing.NOOP_SPAN
            if span.recording:
                dspan = tracing.start_span(
                    "batcher.drain", parent=span.context, start_ns=t0,
                    cpu_start_ns=tracing.cpu_ns(), group=item[0],
                    blocks=len(item[2].jobs))
            try:
                drain(dspan, *item)
            finally:
                release(item[2])
                t1 = tracing.now_ns()
                stages["drain"] += (t1 - t0) / 1e9
                dspan.end(t1, tracing.cpu_ns() if dspan.recording else None)

        def drain(dspan, gi, gkey, cached, mq, pre, fut):
            try:
                if hasattr(fut, "result"):  # coalescer Future vs tuple
                    # NOT timed as d2h: a coalescer Future's wait
                    # includes the coalescing window + the group's
                    # stacking/dispatch. Where a traced search has to
                    # sleep on it (a launch this thread flushed itself
                    # is done by now) the sleep is a span of its own,
                    # `batcher.await_launch`: the drain waiting for
                    # another thread's flush
                    if dspan.recording and not fut.done():
                        with tracing.start_span(
                                "batcher.await_launch",
                                parent=dspan.context, group=gi) as wspan:
                            out = fut.result()
                            launch = getattr(fut, "launch", None)
                            if launch is not None:
                                wspan.set_attribute("launch", launch)
                        fut = out
                    else:
                        fut = fut.result()
                # the ACTUAL device→host sync: the one fetch of the
                # launch's output array (a fused member's slice makes
                # or awaits its group's) — time exactly this so
                # stage=d2h means transfer, not queue. Watchdog-bounded:
                # a wedged device can hang the SYNC even when the
                # enqueue returned, and that hang must become a fault
                # too.
                t0d = tracing.now_ns()
                c0d = tracing.cpu_ns() if dspan.recording else None

                def _sync(fut=fut):
                    # a fused member's slice fetches the group's array
                    # once a group; a solo launch's is fetched here.
                    # Either way ONE host array, the dense ?agg= counts
                    # behind the rest of it
                    if isinstance(fut, _FusedSlice):
                        return fut.fetch()
                    return self.engine.fetch(fut, mq), 1

                (count, inspected, scores, idx, *agg_counts), out_fetches \
                    = robustness.GUARD.run("d2h", _sync)
            except robustness.DeadlineExceeded:
                # the request's budget ran out mid-drain: the answer
                # goes out PARTIAL — this group's results are dropped,
                # not waited for
                results.metrics.partial = True
                obs.partial_results.inc(reason="deadline")
                return
            except robustness.DeviceFault:
                # the dispatch (or its sync) died on the device — the
                # breaker fault is already booked; resubmit THIS query's
                # share of the group on the byte-identical host path.
                # For a fused dispatch every member future fails and
                # each member's drain resubmits its own query here.
                # book_skips=False: the main loop already counted this
                # group's skipped blocks/reasons at prepare time.
                host_route(gi, book_skips=False)
                return
            t1d = tracing.now_ns()
            d2h_s = (t1d - t0d) / 1e9
            if dspan.recording:
                # what the `d2h` stage times: the one blocking sync.
                # `out_fetches` is said only where it is not the 1 of a
                # sync that fetched its launch's array (a fused member
                # that found the group's fetched): a served search's
                # self-trace keeps 64 pairs in key order and every key
                # before `service.name` costs it one
                attrs = {} if out_fetches == 1 else {
                    "out_fetches": out_fetches}
                tracing.record_span("batcher.sync", t0d, t1d,
                                    parent=dspan.context, cpu_start_ns=c0d,
                                    cpu_end_ns=tracing.cpu_ns(), group=gi,
                                    **attrs)
            profile.observe_stage(
                "d2h", "batched", d2h_s,
                nbytes=scores.nbytes + idx.nbytes + 8, spanned=True)
            if qs is not None:
                # the wait THIS query paid for its results (for a fused
                # group the first drainer pays the real sync); count=False
                # — the dispatch itself was already attributed at launch
                qs.add_device_stages({"d2h": d2h_s}, count=False)
                qs.add_inspected(blocks=pre["inspected_blocks"],
                                 nbytes=pre["inspected_bytes"],
                                 placement="device")
                # staged bytes this group's scan actually read, both
                # sides of the packed-residency split (physical ==
                # logical when packing is off)
                b = cached.batch
                qs.add_staged(b.device_nbytes,
                              int(b.logical_device_nbytes
                                  or b.device_nbytes))
            # harvest the uploaded per-query tables AFTER the dispatch
            # ran: under coalescing the flush (and its H2D upload) can
            # happen on the window-timer thread, after submit returned —
            # harvesting at submit time saw nothing and repeat predicates
            # re-uploaded their [B,T]/[B,T,R,2] tables every dispatch.
            # A fused dispatch uploads the STACKED tables instead, so
            # per-query params exist only when the single-query kernel
            # ran (solo flush or coalescing disabled)
            new_dp = getattr(mq, "_device_params", None)
            if new_dp is not None:
                # the uploaded query tables live in HBM: account them
                # against the batch so the cache_bytes budget sees
                # per-predicate device memory, not just page arrays.
                # On a mesh they are replicated: every device holds the
                # whole of each (`nbytes` is its logical size), and the
                # budget is one sum over the mesh's devices
                dpb = int(sum(getattr(a, "nbytes", 0) for a in new_dp)
                          ) * self.engine.n_shards
                with self._lock:
                    if pre.get("device_params") is None:
                        pre["device_params"] = new_dp
                        pre["device_params_bytes"] = dpb
                        cached.nbytes += dpb
                        # residency guard (same as the memo eviction): dp
                        # bytes charged to an already-evicted batch would
                        # inflate the budget with memory the next
                        # eviction can never reclaim
                        if self._cache.get(gkey) is cached:
                            self._cache_total += dpb
                            self._evict_hbm_locked()
            inspected -= pre["entries_skipped"]
            results.metrics.inspected_blocks += pre["inspected_blocks"]
            results.metrics.inspected_bytes += pre["inspected_bytes"]
            results.metrics.truncated_entries += pre["truncated"]
            results.metrics.inspected_traces += max(0, inspected)
            for m in self.engine.results(cached.batch, mq, scores, idx):
                results.add(m)
            if agg_counts:
                results.add_agg(mq.agg_stage.decode(agg_counts[0]))

        def _skip_reason_counts(skip, reasons) -> dict:
            """reason -> count for the skipped blocks: the header prune
            knows why (time_range/duration); anything skipped beyond it
            was dictionary-pruned (no value can satisfy a term)."""
            out: dict = {}
            for s, r in zip(skip, reasons):
                if s:
                    key = r or "dict"
                    out[key] = out.get(key, 0) + 1
            return out

        def prepare(group, holder, skip, reasons,
                    host_only: bool = False) -> dict:
            """O(group) predicate work, memoized per (batch, predicate):
            per-block compile + metric sums. `skip` is the header-prune
            list (already computed for the pre-staging fast path);
            `reasons` its why-column, carried into the per-query stats'
            skipped-blocks breakdown. `holder` is the staged BlockBatch
            on the device path or the HostBatch on the breaker's
            host-fallback route (both carry .blocks and memoize the
            dictionary grouping); `host_only` keeps the compile off the
            device entirely (see compile_multi)."""
            mq = compile_multi(list(holder.blocks), req,
                               skip=skip, cache_on=holder,
                               host_only=host_only)
            if mq is None:
                return {"all_skip": True, "skipped": len(group),
                        "skip_reasons": _skip_reason_counts(
                            [True] * len(group), reasons)}
            # structural plan (gated: structural_query reads ONE
            # attribute when search_structural_enabled is off). Compiled
            # per (batch, predicate) and memoized with this pre dict; the
            # host route compiles its own host-only twin (range tables,
            # no staged dictionary — byte-identical verdicts).
            st = None
            expr = _structural.structural_query(req)
            if expr is not None:
                blocks = list(holder.blocks)
                # spanned on structural searches alone (a flat search
                # writes no new span name: PERF.md section 7 h11)
                with tracing.start_span("structural.compile") as cspan:
                    st = _structural.compile_structural(
                        expr, blocks, cache_on=holder,
                        staged_dicts=(None if host_only else
                                      getattr(holder, "staged_dicts",
                                              None)),
                        host_only=host_only,
                        entry_kv_slots=blocks[0].geometry.kv_per_entry)
                    cspan.set_attributes(
                        nodes=len(st.node_info), blocks=len(blocks),
                        terms=(0 if st.term_keys is None
                               else int(st.term_keys.shape[1])))
            # dictionary-pruned jobs (term key -1 across all terms) count
            # as skipped; under the exhaustive flag nothing is skipped —
            # every page is scanned by definition
            if not exhaustive and mq.n_terms:
                # the tables' block axis is padded to its bucket
                # (multiblock.block_bucket): the group's rows come first
                dict_pruned = (mq.term_keys[:len(group)] == -1).all(axis=1)
                skip = [s or bool(dict_pruned[i])
                        for i, s in enumerate(skip)]
            pre = {
                "skip_reasons": _skip_reason_counts(skip, reasons),
                "all_skip": False,
                "term_keys": mq.term_keys,
                "val_ranges": mq.val_ranges,
                "val_hits": mq.val_hits,
                "block_group": mq.block_group,
                "probes": mq.probes,
                "structural": st,
                "n_terms": mq.n_terms,
                "dur_lo": mq.dur_lo, "dur_hi": mq.dur_hi,
                "win_start": mq.win_start, "win_end": mq.win_end,
                "skipped": sum(skip),
                "entries_skipped": sum(
                    j.n_entries for j, s in zip(group, skip) if s),
                "inspected_blocks": sum(1 for s in skip if not s),
                "inspected_bytes": sum(
                    j.bytes_est for j, s in zip(group, skip) if not s),
                # write-time kv-slot truncation surfaces on the query it
                # may have falsified; attributed to the page-0 job so a
                # block split across range jobs counts once
                "truncated": sum(
                    int(j.header.get("truncated_entries", 0) or 0)
                    for j, s in zip(group, skip)
                    if not s and j.key[1] == 0),
            }
            return pre

        sig = _predicate_sig(req)
        # ?agg= opt-in (gated: one attribute read + one dict probe while
        # analytics is off). The AggStage itself is staged lazily at
        # dispatch time, memoized per batch — prepare() memos stay
        # shareable with non-agg requests because `pre` carries no agg
        # state
        want_agg = ANALYTICS.enabled and agg_requested(req)

        def host_route(gi, book_skips=True):
            """Scan one group ENTIRELY on the host path: this member is
            not the group's owner (owner-routed HBM), the breaker is
            open/half-open without a probe token, or this group's device
            dispatch already faulted (drain resubmit). Host-tier staging
            (no device put), host-only compile (range tables), the same
            kernel pinned to the CPU backend — results byte-identical to
            the device route (see host_scan). Accounting mirrors the
            device drain, with bytes booked placement=host: the answer
            is COMPLETE, not partial — only the placement moved.
            `book_skips=False` on resubmit paths whose main-loop pass
            already counted this group's skipped blocks/reasons —
            re-booking would inflate skipped_blocks and break the
            wedged-vs-healthy identity whenever a block dict-prunes."""
            t0 = tracing.now_ns()
            c0 = tracing.cpu_ns() if span.recording else None
            group, gkey, hdr_reasons = groups[gi], gkeys[gi], reasons[gi]
            try:
                host = self._host_batch(group)
                skip = [r is not None for r in hdr_reasons]
                hq = getattr(host, "_host_query_cache", None)
                if hq is None:
                    hq = host._host_query_cache = OrderedDict()
                with self._lock:
                    pre = hq.get(sig)
                    if pre is not None:
                        hq.move_to_end(sig)
                if pre is None:
                    pre = prepare(group, host, skip, hdr_reasons,
                                  host_only=True)
                    with self._lock:
                        hq[sig] = pre
                        while len(hq) > _QUERY_CACHE_MAX:
                            hq.popitem(last=False)
                if qs is not None:
                    qs.add_cache("device_fallback")
                    if book_skips:
                        for r, n in pre.get("skip_reasons", {}).items():
                            qs.add_skip(r, n)
                if book_skips:
                    results.metrics.skipped_blocks += pre.get("skipped", 0)
                if pre["all_skip"]:
                    return
                from .multiblock import MultiQuery

                mq = MultiQuery(
                    term_keys=pre["term_keys"],
                    val_ranges=pre["val_ranges"],
                    dur_lo=pre["dur_lo"], dur_hi=pre["dur_hi"],
                    win_start=pre["win_start"], win_end=pre["win_end"],
                    limit=req.limit or 20, n_terms=pre["n_terms"],
                    structural=pre.get("structural"))
                if want_agg:
                    mq.agg_stage = ANALYTICS.stage_for_batch(host)
                if qs is not None and pre.get("structural") is not None:
                    qs.add_structural(pre["structural"])
                count, inspected, scores, idx, *agg_counts = host_scan(
                    host, mq, resolve_top_k(self.engine.top_k, mq.limit))
                # the CPU-pinned copies host_scan memoized are real RAM:
                # charge them to the host-tier budget (evicting the
                # entry releases both — _load_host subtracts the
                # recorded cpu bytes alongside nbytes). Delta-charged:
                # the span-column memo (_cpu_span_staged) can appear on
                # a LATER structural query after the cat arrays were
                # already charged, and it must not pin unaccounted RAM.
                cpu_b = sum(
                    int(a.nbytes)
                    for memo in (getattr(host, "_cpu_staged", None),
                                 getattr(host, "_cpu_span_staged", None))
                    if memo is not None for a in memo.values())
                if cpu_b:
                    with self._lock:
                        if self._host_cache.get(gkey) is host:
                            prev = self._cpu_staged_bytes.get(gkey, 0)
                            if cpu_b > prev:
                                self._cpu_staged_bytes[gkey] = cpu_b
                                self._host_total += cpu_b - prev
                                self._evict_host_locked()
                                self._publish_gauges_locked()
                obs.scan_dispatches.inc(mode="host_fallback", shards=1)
                inspected -= pre["entries_skipped"]
                results.metrics.inspected_blocks += pre["inspected_blocks"]
                results.metrics.inspected_bytes += pre["inspected_bytes"]
                results.metrics.truncated_entries += pre["truncated"]
                results.metrics.inspected_traces += max(0, inspected)
                if qs is not None:
                    qs.add_inspected(blocks=pre["inspected_blocks"],
                                     nbytes=pre["inspected_bytes"],
                                     placement="host")
                    qs.add_staged(host.cat_nbytes,
                                  int(host.cat_logical_nbytes
                                      or host.cat_nbytes))
                for m in self.engine.results(host, mq, scores, idx):
                    results.add(m)
                if agg_counts:
                    results.add_agg(mq.agg_stage.decode(agg_counts[0]))
            finally:
                book("host_fallback", t0, c0, gi)

        # what the header prune decided, by plan index, once it is known
        # to this search: the per-job skip REASON list (None = scan the
        # job), and whether the group is live (a group whose every job
        # has a reason is dead and costs no IO and no HBM). Decided
        # lazily, as far as the walk has to look: a search that fills
        # its limit in its first groups never reads the headers of the
        # rest
        reasons: list = [None] * len(groups)
        live: list = [None] * len(groups)

        def header_known_locked(gi):
            """Is group `gi` live, if this search, or the memo of an
            earlier one with its predicate, has decided it; else None —
            caller holds self._lock."""
            if live[gi] is None:
                why = self._prune_cache.get((gkeys[gi], sig))
                if why is not None:
                    self._prune_cache.move_to_end((gkeys[gi], sig))
                    reasons[gi], live[gi] = why, not all(why)
            return live[gi]

        def decide_header(gi):
            """Header-only prune of a group no memo knows: read its
            blocks' headers (time window, duration rollup) and keep the
            answer for every later search with this predicate. Only
            this, a miss, writes a `batcher.header_prune` span."""
            t0 = tracing.now_ns()
            c0 = tracing.cpu_ns() if span.recording else None
            why = [block_header_skip_reason(j.header, req)
                   for j in groups[gi]]
            reasons[gi], live[gi] = why, not all(why)
            with self._lock:
                self._prune_cache[(gkeys[gi], sig)] = why
                while len(self._prune_cache) > _PRUNE_CACHE_MAX:
                    self._prune_cache.popitem(last=False)
            book("header_prune", t0, c0, gi)

        def owned(gi):
            """Is group `gi` this member's to hold in HBM: one owned
            elsewhere takes the host route, in plan order, and is never
            resident, joined or staged ahead."""
            if OWNERSHIP.enabled:
                return OWNERSHIP.owns_group(gkeys[gi])
            return True

        def miss_event_locked(gkey):
            return ("hbm_miss_host_hit" if gkey in self._host_cache
                    else "hbm_miss_cold")

        def claim_locked(gi, resident):
            """The walk takes group `gi` — caller holds self._lock, the
            one that chose it. Returns (gi, pick, entry, future, event):
            `pick` is who pays the put, nobody (`resident`), another
            search (`joined`) or this one (`staged`: here, or by its
            look-ahead, `future`); `entry` the resident entry, pinned,
            or None (stage it); `event` the cache event as this search
            saw it (the global counters cannot say whose re-stage it
            was). A look-ahead's group keeps the event judged when it
            was submitted: the look-ahead has since inserted the batch,
            and residency now would report this search's own cold stage
            as a hit."""
            gkey = gkeys[gi]
            fut, event = prefetched.pop(gkey, (None, None))
            if fut is not None and not fut.cancel():
                return gi, "staged", None, fut, event
            # no look-ahead, or one that never ran: as if unasked
            if resident:
                entry = self._resident_locked(gkey, pin=True)
                pinned.append(entry)
                return gi, "resident", entry, None, "hbm_hit"
            pick = "joined" if gkey in self._staging else "staged"
            return gi, pick, None, None, miss_event_locked(gkey)

        def take_next():
            """The search's next group, chosen from the cache as it is
            NOW, not as it was when the search began: of the live groups
            it has not taken, the first in plan order that is resident;
            if none is, the first that another thread is staging (the
            search waits on that put, `_staged` does not make a second);
            else the first in plan order, staged by this search.
            Concurrent searches over a tenant larger than the budget so
            walk towards what is resident and share each other's puts,
            where each walking a list fixed at its start staged the
            same group once apiece and lost, to the others' evictions,
            residents it had not reached. A resident pick is pinned
            under the lock that chose it. Where every group is resident
            this yields plan order at every step. Dead groups the scan
            passes are booked as skipped and leave `remaining`. Returns
            what `claim_locked` does, or None where no live group
            remains."""
            while True:
                dead, undecided, taken = [], None, None
                with self._lock:
                    blocked = robustness.BREAKER.blocking()
                    resident = first = joined = None
                    for i in remaining:
                        alive = header_known_locked(i)
                        if alive is None:
                            undecided = i
                            break
                        if not alive:
                            dead.append(i)
                            continue
                        if first is None:
                            first = i
                        if blocked:
                            break   # the host route: plan order, no pin
                        if not owned(i):
                            continue
                        if gkeys[i] in self._cache:
                            resident = i
                            break
                        if joined is None and (gkeys[i] in self._staging
                                               or gkeys[i] in prefetched):
                            joined = i
                    if undecided is None and first is not None:
                        taken = (claim_locked(resident, True)
                                 if resident is not None else
                                 claim_locked(first if joined is None
                                              else joined, False))
                for i in dead:
                    remaining.remove(i)
                    results.metrics.skipped_blocks += len(reasons[i])
                    if qs is not None:
                        for r in reasons[i]:
                            qs.add_skip(r)
                if undecided is not None:
                    decide_header(undecided)
                    continue
                if taken is not None:
                    remaining.remove(taken[0])
                return taken

        def submit_prefetch():
            """One-slot staging look-ahead: once no group the search has
            not taken is resident, stage in a background thread the
            first of them that nobody is staging, while the group just
            taken is scanned (H2D overlaps compute). Not before: while
            the walk has residents to take, a put would make the LRU
            drop a group, as likely as not one this search has not
            reached, and whoever stages the missing group meanwhile
            stages it for this search too (on a v5e a look-ahead from
            the search's first step made 0.98 puts a search where this
            makes 0.39, PERF.md section 6). A resident group whose
            headers nobody has read counts as one the walk may take.
            One slot: nothing new is asked for until the walk took what
            the last one staged. The group is pinned by the put
            (`_staged(pin=True)`), for the search that asked."""
            if prefetched or robustness.BREAKER.blocking():
                return  # no lookahead H2D at a blocked device
            while True:
                with self._lock:
                    gi = None
                    for i in remaining:
                        if header_known_locked(i) is False or not owned(i):
                            continue
                        if gkeys[i] in self._cache:
                            return
                        if gi is None and gkeys[i] not in self._staging:
                            gi = i
                    if gi is None:
                        return
                    event = miss_event_locked(gkeys[gi])
                if live[gi]:
                    break
                decide_header(gi)
            prefetched[gkeys[gi]] = (
                self._prefetcher.submit(
                    self._staged, groups[gi], True,
                    span.context if span.recording else None),
                event)

        with tracing.start_span("batcher.Search") as span:
            # plan indices not taken yet, in plan order. Under an early
            # quit the SCANNED subset (and so the returned set when limit
            # truncates) depends on cache residency at each step — same
            # stance as the reference's goroutine fan-out, where the quit
            # channel freezes whichever jobs happened to finish first
            # (modules/frontend/searchsharding.go + results.go quit)
            remaining = list(range(len(groups)))
            while remaining:
                if results.complete:
                    break
                if robustness.deadline.expired():
                    # the request's budget is gone: stop queueing more
                    # sub-scans behind whatever is slow (a dead device,
                    # a cold cache) — the answer goes out PARTIAL now
                    results.metrics.partial = True
                    obs.partial_results.inc(reason="deadline")
                    break
                taken = take_next()
                if taken is None:
                    break   # what remained was dead
                gi, pick, cached, fut_staged, _event = taken
                group, gkey, hdr_reasons = groups[gi], gkeys[gi], reasons[gi]
                if OWNERSHIP.enabled:
                    # owner-routed HBM: a group this member doesn't own
                    # serves from the byte-identical host route — a
                    # non-owner never stages a duplicate device copy
                    # (docs/search-hbm-ownership.md); the owner's serve
                    # proceeds below, device-resident. Every served
                    # group feeds the heat table (one attribute read
                    # while replication is off): the batcher's dispatch
                    # loop is the one site that observes every scan,
                    # and a group crossing hot_rate here promotes to
                    # its replica set for hedged dispatch
                    OWNERSHIP.record_access(str(gkey[0][0]))
                    if not OWNERSHIP.owns_group(gkey):
                        obs.hbm_owner_routed.inc(route="non_owner_host")
                        if qs is not None:
                            qs.add_cache("non_owner_route")
                        host_route(gi)
                        continue
                if not robustness.BREAKER.allow_device():
                    # breaker open (or half-open with its probe tokens
                    # spent): this group runs the byte-identical host
                    # route — no staging put, no device dispatch
                    if cached is not None:
                        release(cached)   # it opened since the pick
                    if fut_staged is not None:
                        # a look-ahead from before it opened: search()'s
                        # finally gives its pin back
                        prefetched[gkey] = (fut_staged, _event)
                    host_route(gi)
                    continue
                if OWNERSHIP.enabled:
                    # counted AFTER the breaker gate: route=owner means
                    # a device-resident serve, and during a wedged-owner
                    # incident the owned groups above fell into the
                    # breaker's host route instead
                    obs.hbm_owner_routed.inc(route="owner")
                # memo lookup needs the staged batch's identity; the memo
                # itself lives on the cached batch and, while the group is
                # evicted, on its host-tier entry (`HostBatch.query_memo`):
                # it dies with the last of the two
                t0 = tracing.now_ns()
                c0 = tracing.cpu_ns() if span.recording else None
                if cached is None:
                    try:
                        # pinned from here (a look-ahead took its pin
                        # when it staged) until this group's own drain
                        cached = (fut_staged.result()
                                  if fut_staged is not None
                                  else self._staged(group, pin=True))
                    except robustness.DeviceFault:
                        # the staging H2D hit the wedged device (fault
                        # booked): host tier already holds the stacked
                        # arrays, answer from there
                        book("staging", t0, c0, gi)
                        host_route(gi)
                        continue
                    pinned.append(cached)
                book("staging", t0, c0, gi, cache=_event, pick=pick)
                if (span.recording and pick == "staged"
                        and cached.batch.span_put_ns
                        and _structural.STRUCTURAL_QUERY_TAG in req.tags):
                    # the put of the group's span columns, from its own
                    # stamps: only a structural search that paid for it
                    # writes the span (a flat search's trace gets no new
                    # span name: PERF.md section 7 h11)
                    b = cached.batch
                    rows = int(b.span_device["span_trace"].shape[0])
                    tracing.record_span(
                        "batcher.stage_spans", *b.span_put_ns,
                        parent=span.context, span_rows=rows,
                        pad_rows=rows - sum(x.n_spans for x in b.blocks),
                        bytes=self._span_bytes(b))
                obs.group_picks.inc(pick=pick)
                if qs is not None:
                    qs.add_cache(_event)
                    if _event != "hbm_hit" and cached.batch.staged_dicts:
                        qs.add_cache("probe_dict_staged",
                                     len(cached.batch.staged_dicts))
                submit_prefetch()
                with self._lock:
                    pre = cached.query_cache.get(sig)
                    if pre is not None:
                        cached.query_cache.move_to_end(sig)
                obs.prepare_memo.inc(
                    result="miss" if pre is None else "hit")
                if pre is None:
                    t0 = tracing.now_ns()
                    c0 = tracing.cpu_ns() if span.recording else None
                    # attributed: query compilation can fire the device
                    # dictionary probe (mode=dict_probe) — that dispatch
                    # belongs to this query's bill (no wall fallback:
                    # most of prepare() is host compile work)
                    with query_stats.attributed_dispatch(
                            qs, fallback_wall=False):
                        pre = prepare(group, cached.batch,
                                      [r is not None for r in hdr_reasons],
                                      hdr_reasons)
                    book("prepare", t0, c0, gi, terms=pre.get("n_terms", 0),
                         probes=pre.pop("probes", None))
                    # a hit mask the memo keeps is HBM like a predicate's
                    # uploaded tables: charged to the batch, so the
                    # budget sees it and an eviction gives it back
                    mb = int(getattr(pre.get("val_hits"), "nbytes", 0))
                    pre["mask_bytes"] = mb
                    with self._lock:
                        cached.query_cache[sig] = pre
                        resident = self._cache.get(gkey) is cached
                        cached.nbytes += mb
                        if resident:
                            cached.mask_bytes += mb
                            self._cache_total += mb
                            MASK_BYTES.add("memo", mb)
                        while len(cached.query_cache) > _QUERY_CACHE_MAX:
                            _, old = cached.query_cache.popitem(last=False)
                            dpb = (old.get("device_params_bytes", 0)
                                   + old["mask_bytes"])
                            cached.nbytes -= dpb
                            # the shared budget only tracks batches still
                            # resident: a concurrent eviction already
                            # removed cached.nbytes (dp bytes included)
                            # wholesale, so adjusting again would
                            # double-subtract and drift the budget
                            if self._cache.get(gkey) is cached:
                                self._cache_total -= dpb
                                cached.mask_bytes -= old["mask_bytes"]
                                MASK_BYTES.add("memo", -old["mask_bytes"])
                        if mb and resident:
                            self._evict_hbm_locked()
                if qs is not None:
                    for r, n in pre.get("skip_reasons", {}).items():
                        qs.add_skip(r, n)
                if pre["all_skip"]:
                    results.metrics.skipped_blocks += pre["skipped"]
                    release(cached)
                    continue
                from .multiblock import MultiQuery

                mq = MultiQuery(
                    term_keys=pre["term_keys"], val_ranges=pre["val_ranges"],
                    dur_lo=pre["dur_lo"], dur_hi=pre["dur_hi"],
                    win_start=pre["win_start"], win_end=pre["win_end"],
                    limit=req.limit or 20, n_terms=pre["n_terms"],
                    val_hits=pre.get("val_hits"),
                    block_group=pre.get("block_group"),
                    structural=pre.get("structural"))
                if want_agg:
                    # memoized per batch: repeat ?agg= queries over a
                    # resident batch pay one attribute read, and every
                    # route (direct, coalesced, host resubmit) decodes
                    # against the same service table
                    mq.agg_stage = ANALYTICS.stage_for_batch(cached.batch)
                if qs is not None and pre.get("structural") is not None:
                    # explain plan registration: node cost weights merge
                    # across this query's groups; measured device time
                    # apportions over them at finalize
                    qs.add_structural(pre["structural"])
                dp = pre.get("device_params")
                if dp is not None:
                    # repeated predicates reuse the H2D-uploaded query
                    # tables instead of re-uploading a [B,T] table for
                    # 10K blocks on every dispatch
                    mq._device_params = dp
                results.metrics.skipped_blocks += pre["skipped"]
                t0 = tracing.now_ns()
                c0 = tracing.cpu_ns() if span.recording else None
                if self.coalescer is not None:
                    # concurrent peers hitting this batch within the
                    # window share ONE fused kernel launch; a dispatch
                    # with no possible same-batch peer (solo search, or
                    # a sibling sub-request over a disjoint batch) flushes
                    # immediately (no added latency). Structural queries
                    # group by PLAN SHAPE inside submit(): same-plan
                    # peers stack along the fused query axis when
                    # search_structural_stack_enabled, anything else
                    # flushes solo (stack_events says which).
                    with self._lock:
                        peers = (self._interest.get(gkey, 1)
                                 + self._unplanned)
                    fut = self.coalescer.submit(
                        cached.batch, mq,
                        resolve_top_k(self.engine.top_k, mq.limit),
                        peers=peers)
                else:
                    try:
                        with query_stats.attributed_dispatch(qs):
                            fut = self.engine.scan_async(cached.batch, mq)
                        if span.recording:
                            profile.DEVICE_TIMELINE.watch(
                                fut, span.context, 1, len(group), "multi")
                        start_fetch(fut)  # D2H begins now, overlapping
                    except robustness.DeviceFault:
                        # direct-path dispatch died at submit (fault
                        # booked): answer this group on host NOW — its
                        # skips were already counted above, so the
                        # resubmit must not re-book them. Interest for
                        # this gkey is released by the outer finally.
                        book("dispatch", t0, c0, gi)
                        release(cached)
                        host_route(gi, book_skips=False)
                        continue
                book("dispatch", t0, c0, gi)
                dispatches += 1
                inflight.append((gi, gkey, cached, mq, pre, fut))
                # this search never returns to this batch: release its
                # interest NOW so later peers don't arm windows for a
                # fusion that can no longer happen (a parked query still
                # fuses — joiners find the pending group itself, not the
                # hint). The outer finally releases whatever never
                # dispatched (skipped groups, early quit)
                with self._lock:
                    n = self._interest.get(gkey, 0) - 1
                    if n <= 0:
                        self._interest.pop(gkey, None)
                    else:
                        self._interest[gkey] = n
                try:
                    interest.remove(gkey)
                except ValueError:
                    pass
                while len(inflight) >= self.pipeline_depth:
                    drain_one()
            while inflight:
                if results.complete:
                    inflight.clear()   # their pins: search()'s finally
                    break
                drain_one()
            if tenant is not None:
                span.set_attribute("tenant", tenant)
            span.set_attributes(groups=len(groups), scan_dispatches=dispatches,
                                inspected_blocks=results.metrics.inspected_blocks,
                                skipped_blocks=results.metrics.skipped_blocks)
        if self.coalescer is None:
            # with the coalescer active the LAUNCH counters are kept at
            # flush time (mode="batched" solo, mode="coalesced" fused) —
            # counting submits here would double-book shared launches
            obs.scan_dispatches.inc(dispatches, mode="batched",
                                    shards=self.engine.n_shards)
        if qs is not None:
            for k, v in stages.items():
                qs.add_stage(k, v)
        self.last_dispatches = dispatches
        self.last_scan = {
            "total_ms": round((tracing.now_ns() - t_search0) / 1e6, 3),
            "stages_ms": {k: round(v * 1000, 3) for k, v in stages.items()},
            "scan_dispatches": dispatches,
            "groups": len(groups),
            "inspected_blocks": results.metrics.inspected_blocks,
            "skipped_blocks": results.metrics.skipped_blocks,
        }
        return results

    def debug_stats(self) -> dict:
        """Operator-facing snapshot for /debug/scan: the last search's
        per-stage breakdown plus cache occupancy — the numbers that
        answer "why is this query slow" without a profiler attached."""
        with self._lock:
            return {
                "last_scan": getattr(self, "last_scan", None),
                "hbm_cache": {
                    "batches": len(self._cache),
                    "bytes": self._cache_total,
                    "logical_bytes": self._cache_logical,
                    "budget_bytes": self.cache_bytes,
                },
                "host_cache": {
                    "batches": len(self._host_cache),
                    "bytes": self._host_total,
                    "logical_bytes": self._host_logical,
                    "budget_bytes": self.host_cache_bytes,
                },
                "memo": {
                    "prune_entries": len(self._prune_cache),
                    "plan_entries": len(self._plan_cache),
                    "warmed_shapes": len(self._warmed_shapes),
                },
                "coalesce": (self.coalescer.stats()
                             if self.coalescer is not None else None),
            }
