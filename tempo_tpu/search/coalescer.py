"""Cross-request query coalescing: searches whose next dispatch targets
the same staged batch share one fused kernel launch. The search loop
(`batcher.py`) submits one compiled query a group and drains the Future
it gets back; what is here knows the engine and nothing of the batcher.
"""

from __future__ import annotations

import threading

from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import profile
from tempo_tpu.observability import tracing

from . import query_stats
from . import structural as _structural
from .engine import start_fetch
from .multiblock import (
    WIDE_RANGES, MultiBlockEngine, block_bucket, stack_queries,
)


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
            # a ?agg= member launches solo, NOW: the reduction sorts the
            # group's whole key column a member, a fused launch sorts
            # one column a member all the same (vmap) and the batched
            # sort is the slower (4,096 pages on a v5e: 9.0 ms solo;
            # fused 54 / 63 / 107 ms at 2 / 4 / 8 members,
            # scripts/red_bench.py, PR 48), and every fused (Q, T, R)
            # is one more program of 12-16 s of cold compile that a
            # window meets before any warm-up did. Solo, a search's
            # programs are the ones its first launch compiled
            with self._lock:
                self._gen += 1
                grp = _PendingCoalesce(batch, self._gen)
            grp.items.append((mq, top_k, fut, tracing.now_ns(),
                              query_stats.current(), parent))
            self._run(grp)
            return fut
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
