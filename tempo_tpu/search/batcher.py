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

import concurrent.futures
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass

import numpy as np

from tempo_tpu import robustness
from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import profile
from tempo_tpu.observability import tracing

from . import query_stats
from . import structural as _structural
from .analytics import ANALYTICS, agg_requested
from .coalescer import QueryCoalescer, _FusedSlice
from .engine import DEFAULT_TOP_K, fetch_scan_out, resolve_top_k, start_fetch
from .group_cache import GroupCache
from .ownership import OWNERSHIP
from .multiblock import (
    MultiBlockEngine, MultiQuery, block_bucket, compile_multi,
)
from .pipeline import block_header_skip_reason, is_exhaustive, probe_summary
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
    from .multiblock import batch_scan_kernel   # at call time: tests wrap it

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
        self.pipeline_depth = max(1, pipeline_depth)
        # where staged groups live, and the lock this class shares with
        # it: plan cache, prune memo and the interest counts below are
        # read under the same hold as residency (group_cache.py)
        self.cache = GroupCache(self.engine, cache_bytes, host_cache_bytes,
                                io_workers)
        self._warmed_shapes: set = set()  # compile-warm dedupe
        self._prune_cache: OrderedDict = OrderedDict()
        self._plan_cache: OrderedDict = OrderedDict()
        # staging look-ahead: a search's next missing group is staged
        # while those it took are scanned (H2D overlaps compute). More
        # than one thread, so that concurrent searches' look-aheads do
        # not queue behind each other; the cache dedupes racing stages
        self._prefetcher = concurrent.futures.ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="stage-prefetch")
        # cross-request query coalescing (coalescer.py);
        # coalesce_max_queries <= 1 disables it: every submit then
        # dispatches directly.
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
        # dispatch SUBMITS of the last search; launches, which searches
        # share under coalescing, are counted in scan_dispatches{mode}
        self.last_dispatches = 0
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

    def prewarm(self, groups: list[list[ScanJob]],
                warm_compile: bool = True,
                stop: threading.Event | None = None) -> int:
        """Stage groups ahead of queries (called in the background after
        a poll): fills the host tier + HBM up to their budgets in plan
        order, and optionally warms the XLA compile cache for the
        staged shapes with a throwaway dispatch, so the first real query
        pays neither staging nor compile. Returns groups staged."""
        staged = 0
        budget = self.cache.cache_bytes
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
            resident = self.cache.resident(gkey) is not None
            try:
                cached = self.cache.staged(group)
            except Exception:  # noqa: BLE001 — prewarm is best-effort
                continue
            # only staging WORK spends the budget: charging resident
            # hits would spend it on the warm prefix at every poll
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

    def _warm_compile(self, cached) -> None:
        """Throwaway dispatches to populate the jit cache for this
        batch's shape at the common term counts (0 = duration/window
        only, 2 = the typical tag AND). The jit cache keys on the PADDED
        shape (pow2-bucketed) — warming is deduped per shape signature,
        or a 100-group tenant would device-scan the whole corpus ~200x
        for ~log2 distinct compiles."""
        # dtypes are part of the jit cache key too: dictionary-size
        # narrowing means two same-shaped batches can carry int8 vs
        # int16 kv columns and compile separately; the packed-residency
        # width descriptor likewise
        shape_sig = (cached.batch.device["entry_valid"].shape,
                     cached.batch.device["kv_key"].shape,
                     str(cached.batch.device["kv_key"].dtype),
                     str(cached.batch.device["kv_val"].dtype),
                     cached.batch.widths,
                     block_bucket(len(cached.batch.blocks)))
        with self.cache.group_lock:
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
        with self.cache.group_lock:
            self._unplanned += 1
        pinned: list = []            # entries pinned now, one pin each
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
            # the cache's dedupe and gives its pin back when it does
            for f, _ev in prefetched.values():
                if not f.cancel():
                    f.add_done_callback(self.cache.unpin_unused)
            with self.cache.group_lock:
                if planned[0]:
                    for k in interest:
                        self._lose_interest_locked(k)
                else:  # died before the plan resolved
                    self._unplanned -= 1
                # whatever an exception or an early quit left in flight
                self.cache.unpin_locked(pinned)

    def _lose_interest_locked(self, gkey: tuple) -> None:
        """One search fewer plans to scan `gkey` — caller holds the
        cache's lock."""
        n = self._interest.get(gkey, 0) - 1
        if n <= 0:
            self._interest.pop(gkey, None)
        else:
            self._interest[gkey] = n

    def _search_impl(self, jobs: list[ScanJob], req,
                     results: SearchResults | None,
                     plan_key, groups: list | None,
                     pinned: list, prefetched: dict, interest: list,
                     planned: list,
                     tenant: str | None = None) -> SearchResults:
        results = results or SearchResults.for_request(req)
        exhaustive = is_exhaustive(req)
        cache = self.cache
        # the active per-query stats (None when the layer is off), read
        # ONCE: every recording site below is behind this None check
        qs = query_stats.current()
        if groups is None and plan_key is not None:
            # one entry per plan_key[0] (tenant): a stale generation is
            # never hittable again (the epoch only moves forward), so
            # keeping it would just pin 10K dead ScanJobs. The cap is
            # part of the generation: a plan made before the mesh was
            # attached must not outlive it
            tenant_key = plan_key[0]
            gen = (*plan_key[1:], self.group_cap())
            with cache.group_lock:
                hit = self._plan_cache.get(tenant_key)
                if hit is not None and hit[0] == gen:
                    groups = hit[1]
        if groups is None:
            groups = self.plan(jobs)
            if plan_key is not None:
                with cache.group_lock:
                    self._plan_cache[tenant_key] = (gen, groups)
                    while len(self._plan_cache) > 64:
                        self._plan_cache.popitem(last=False)
        # plan is final: declare which batches this search will scan so
        # the coalescer can tell a real same-batch peer from an unrelated
        # concurrent search (which must not make us wait out a window)
        gkeys = [tuple(j.key for j in g) for g in groups]
        with cache.group_lock:
            self._unplanned -= 1
            planned[0] = True
            for k in gkeys:
                self._interest[k] = self._interest.get(k, 0) + 1
            interest.extend(gkeys)
        inflight: deque = deque()
        dispatches = 0
        # per-stage wall time of this search, for /debug/scan: which
        # stage ate a slow query
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
            with cache.group_lock:
                cache.unpin_locked((cached,))

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
                # the device→host sync itself: the one fetch of the
                # launch's output array (a fused member's slice makes
                # or awaits its group's), timed alone so that stage=d2h
                # means transfer, not queue. Watchdog-bounded: a wedged
                # device can hang the sync after the enqueue returned
                t0d = tracing.now_ns()
                c0d = tracing.cpu_ns() if dspan.recording else None

                def _sync(fut=fut):
                    # either way ONE host array, the dense ?agg= counts
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
                # the dispatch (or its sync) died on the device, the
                # breaker fault is booked: resubmit THIS query's share
                # of the group on the byte-identical host path (every
                # member of a fused dispatch does, in its own drain).
                # The main loop already counted the group's skips
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
                # the wait THIS query paid (a fused group's first
                # drainer pays the real sync); the dispatch itself was
                # attributed at launch
                qs.add_device_stages({"d2h": d2h_s}, count=False)
                qs.add_inspected(blocks=pre["inspected_blocks"],
                                 nbytes=pre["inspected_bytes"],
                                 placement="device")
                # staged bytes this scan read, physical and logical
                b = cached.batch
                qs.add_staged(b.device_nbytes,
                              int(b.logical_device_nbytes
                                  or b.device_nbytes))
            # the uploaded per-query tables are harvested AFTER the
            # dispatch ran: under coalescing the flush (and its H2D
            # upload) can happen on the window-timer thread, after
            # submit returned. A fused dispatch uploads the STACKED
            # tables instead, so per-query params exist only when the
            # single-query kernel ran
            new_dp = getattr(mq, "_device_params", None)
            if new_dp is not None:
                cache.memo_params(gkey, cached, pre, new_dp)
            inspected -= pre["entries_skipped"]
            results.metrics.inspected_blocks += pre["inspected_blocks"]
            results.metrics.inspected_bytes += pre["inspected_bytes"]
            results.metrics.truncated_entries += pre["truncated"]
            results.metrics.inspected_traces += max(0, inspected)
            for m in self.engine.results(cached.batch, mq, scores, idx):
                results.add(m)
            if agg_counts:
                # written by aggregating searches only (a flat search's
                # self-trace has no room for a new span name)
                with tracing.start_span("analytics.decode",
                                        parent=dspan.context, group=gi):
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
                host = cache.host_batch(group)
                skip = [r is not None for r in hdr_reasons]
                pre = cache.memo_get(host, sig)
                if pre is None:
                    pre = prepare(group, host, skip, hdr_reasons,
                                  host_only=True)
                    cache.memo_put(gkey, host, sig, pre)
                if qs is not None:
                    qs.add_cache("device_fallback")
                    if book_skips:
                        for r, n in pre.get("skip_reasons", {}).items():
                            qs.add_skip(r, n)
                if book_skips:
                    results.metrics.skipped_blocks += pre.get("skipped", 0)
                if pre["all_skip"]:
                    return
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
                cache.charge_cpu_copies(gkey, host)
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
            caller holds the cache's lock."""
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
            with cache.group_lock:
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
            return ("hbm_miss_host_hit" if cache.in_host_tier_locked(gkey)
                    else "hbm_miss_cold")

        def claim_locked(gi, resident):
            """The walk takes group `gi` — caller holds the cache's lock, the
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
                entry = cache.resident_locked(gkey, pin=True)
                pinned.append(entry)
                return gi, "resident", entry, None, "hbm_hit"
            pick = "joined" if cache.is_staging_locked(gkey) else "staged"
            return gi, pick, None, None, miss_event_locked(gkey)

        def take_next():
            """The search's next group, chosen from the cache as it is
            NOW, not as it was when the search began: of the live groups
            it has not taken, the first in plan order that is resident;
            if none is, the first that another thread is staging (the
            search waits on that put, `cache.staged` makes no second);
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
                with cache.group_lock:
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
                        if cache.is_resident_locked(gkeys[i]):
                            resident = i
                            break
                        if joined is None and (
                                cache.is_staging_locked(gkeys[i])
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
            (`cache.staged(pin=True)`), for the search that asked."""
            if prefetched or robustness.BREAKER.blocking():
                return  # no lookahead H2D at a blocked device
            while True:
                with cache.group_lock:
                    gi = None
                    for i in remaining:
                        if header_known_locked(i) is False or not owned(i):
                            continue
                        if cache.is_resident_locked(gkeys[i]):
                            return
                        if gi is None and not cache.is_staging_locked(
                                gkeys[i]):
                            gi = i
                    if gi is None:
                        return
                    event = miss_event_locked(gkeys[gi])
                if live[gi]:
                    break
                decide_header(gi)
            prefetched[gkeys[gi]] = (
                self._prefetcher.submit(
                    cache.staged, groups[gi], True,
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
                                  else cache.staged(group, pin=True))
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
                        bytes=_structural.span_device_bytes(b.span_device))
                obs.group_picks.inc(pick=pick)
                if qs is not None:
                    qs.add_cache(_event)
                    if _event != "hbm_hit" and cached.batch.staged_dicts:
                        qs.add_cache("probe_dict_staged",
                                     len(cached.batch.staged_dicts))
                submit_prefetch()
                pre = cache.memo_get(cached, sig)
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
                    cache.memo_put(gkey, cached, sig, pre)
                if qs is not None:
                    for r, n in pre.get("skip_reasons", {}).items():
                        qs.add_skip(r, n)
                if pre["all_skip"]:
                    results.metrics.skipped_blocks += pre["skipped"]
                    release(cached)
                    continue
                mq = MultiQuery(
                    term_keys=pre["term_keys"], val_ranges=pre["val_ranges"],
                    dur_lo=pre["dur_lo"], dur_hi=pre["dur_hi"],
                    win_start=pre["win_start"], win_end=pre["win_end"],
                    limit=req.limit or 20, n_terms=pre["n_terms"],
                    val_hits=pre.get("val_hits"),
                    block_group=pre.get("block_group"),
                    structural=pre.get("structural"))
                if want_agg:
                    # the group's key column, part of its cache entry:
                    # built, put and charged once (one flight), so
                    # repeat ?agg= queries over a resident group pay a
                    # lock and an attribute read, and a fused launch's
                    # members share one staged column
                    try:
                        mq.agg_stage = cache.agg_staged(gkey, cached)
                    except robustness.DeviceFault:
                        # the column's put hit the wedged device (fault
                        # booked): this group answers on the host route
                        results.metrics.skipped_blocks += pre["skipped"]
                        release(cached)
                        host_route(gi, book_skips=False)
                        continue
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
                    # peers hitting this batch within the window share
                    # ONE fused launch; with no possible same-batch peer
                    # (a solo search, a sibling sub-request over a
                    # disjoint batch) submit() flushes at once
                    with cache.group_lock:
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
                with cache.group_lock:
                    self._lose_interest_locked(gkey)
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
        with self.cache.group_lock:
            return {
                "last_scan": getattr(self, "last_scan", None),
                **self.cache.debug_stats_locked(),
                "memo": {
                    "prune_entries": len(self._prune_cache),
                    "plan_entries": len(self._plan_cache),
                    "warmed_shapes": len(self._warmed_shapes),
                },
                "coalesce": (self.coalescer.stats()
                             if self.coalescer is not None else None),
            }
