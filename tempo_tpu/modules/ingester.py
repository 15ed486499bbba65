"""Ingester: live traces → WAL head block → completed backend blocks.

Role-equivalent to the reference's modules/ingester (ingester.go:53-416,
instance.go:92-661, flush.go:124-389): per-tenant instances hold live
traces in memory under byte/count limits; a sweep cuts idle/complete
traces into the WAL head block (trace WAL + parallel search WAL); when the
head block is big or old enough it is cut and completed into an immutable
backend block; on restart both WALs replay (SURVEY.md §5 checkpoint).

Divergence from the reference: completed blocks go straight to the shared
backend via TempoDB.complete_block (the reference stages them on an
ingester-local backend first and flushes async with retry/backoff —
flush.go opKindComplete/opKindFlush; collapse is safe in-process because
the backend write is atomic, and the retry queue lives one level up).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

from tempo_tpu import tempopb
from tempo_tpu.db import TempoDB
from tempo_tpu.model.codec import segment_codec_for, CURRENT_ENCODING
from tempo_tpu.search import SearchResults, decode_search_data
from tempo_tpu.search.data import SearchData, search_data_matches
from tempo_tpu.search.live_tier import LIVE_TIER
from tempo_tpu.search.streaming import StreamingSearchBlock, _meta_from_sd
from tempo_tpu.observability import metrics as obs
from tempo_tpu.utils.ids import pad_trace_id
from .overrides import Overrides
from .queue import ExclusiveQueue


class LimitError(Exception):
    pass


class FlushIncompleteError(Exception):
    """flush_all could not get every completing block to the backend.
    Carries what DID flush so shutdown callers can log it; the local WAL
    still holds the rest and must not be deleted."""

    def __init__(self, left_behind: int, completed: list):
        super().__init__(
            f"{left_behind} block(s) could not be flushed to the backend")
        self.left_behind = left_behind
        self.completed = completed


@dataclass
class _LiveTrace:
    segments: list = field(default_factory=list)
    nbytes: int = 0
    last_append: float = 0.0
    # monotonic stamp of the FIRST push — the head of the write-path
    # telemetry record (push -> cut -> flush -> poll visibility). Set
    # from the clock read the push path already makes, so stamping
    # costs nothing even with telemetry disabled.
    first_push: float = 0.0
    # encoded SearchData fragments, decoded+merged LAZILY: the ack path
    # runs per push, while folding is only needed at live-search or cut
    # time — decode-per-push was ~10% of distributor→ingester latency
    search_raw: list = field(default_factory=list)
    _search: SearchData | None = None

    def search_data(self, tid: bytes) -> SearchData | None:
        """Folded search entry (caches; drains the raw fragment list).
        A corrupt fragment is DROPPED here, not raised: this runs inside
        cut_complete_traces after the trace object is already appended —
        an exception would leave the trace live, duplicate its WAL
        append on every retry, and wedge the tenant's sweep forever."""
        if self.search_raw:
            raws, self.search_raw = self.search_raw, []
            for raw in raws:
                try:
                    sd = decode_search_data(raw, tid)
                except Exception:  # noqa: BLE001 — skip corrupt fragment
                    from tempo_tpu.observability import get_logger

                    get_logger().warning(
                        "dropping corrupt search-data fragment for %s",
                        tid.hex()[:16])
                    continue
                if self._search is None:
                    self._search = sd
                else:
                    self._search.merge(sd)
        return self._search


@dataclass
class _Completing:
    """A block awaiting completion, with its per-block retry state
    (reference flush.go:359-389 — each failed op is requeued with its own
    exponential backoff rather than stalling the queue)."""
    blk: object
    search: object
    retry_at: float = 0.0   # monotonic time before which we skip it
    backoff_s: float = 0.0
    in_flight: bool = False  # being completed right now (still queryable)
    attempts: int = 0        # failed completion attempts (retry telemetry)
    cut_at: float = 0.0      # monotonic time the block was cut
    # oldest first_push among the traces in this block (None for
    # replayed blocks — their live traces predate this process)
    oldest_ingest: float | None = None


class TenantInstance:
    # completed blocks stay queryable on the ingester until readers have
    # had time to poll the new block into their blocklists (reference
    # complete_block_timeout, instance.ClearFlushedBlocks :373)
    COMPLETE_BLOCK_TIMEOUT_S = 300.0
    # flush retry backoff envelope (reference flush.go:62-67: 30s initial,
    # exponential, capped)
    FLUSH_BACKOFF_S = 30.0
    FLUSH_BACKOFF_MAX_S = 120.0

    def __init__(self, tenant: str, db: TempoDB, overrides: Overrides):
        self.tenant = tenant
        self.db = db
        self.overrides = overrides
        self.lock = threading.Lock()
        self.live: dict[bytes, _LiveTrace] = {}
        self.codec = segment_codec_for(CURRENT_ENCODING)
        self._new_head()
        self.completing: list[_Completing] = []
        self.recent = []      # [(BlockMeta, completed_at)]

    def _new_head(self):
        self.head = self.db.wal.new_block(self.tenant)
        self.head_search = StreamingSearchBlock(self.head.path + ".search")
        self.head_created = time.monotonic()
        # oldest first_push cut into THIS head block (inf = none yet)
        self.head_oldest = float("inf")

    # ---- write path ----

    def push(self, trace_id: bytes, segment: bytes,
             search_data: bytes = b"") -> None:
        tid = pad_trace_id(trace_id)
        lim = self.overrides.limits(self.tenant)
        now = time.monotonic()
        with self.lock:
            t = self.live.get(tid)
            if t is None:
                if len(self.live) >= lim.max_live_traces:
                    raise LimitError(
                        f"max live traces ({lim.max_live_traces}) reached"
                    )
                t = self.live[tid] = _LiveTrace(first_push=now)
            if t.nbytes + len(segment) > lim.max_bytes_per_trace:
                raise LimitError("max bytes per trace reached")
            t.segments.append(segment)
            t.nbytes += len(segment)
            t.last_append = now
            obs.live_traces.set(len(self.live), tenant=self.tenant)
            if search_data:
                t.search_raw.append(search_data)
                # hot tier: absorb under the instance lock so the tier's
                # live stage mirrors self.live deterministically (a cut
                # between push and absorb would otherwise resurrect the
                # trace in the stage and double-answer forever)
                if LIVE_TIER.enabled:
                    LIVE_TIER.absorb(self.tenant, tid, search_data)

    # ---- sweep / cut (reference CutCompleteTraces instance.go:222) ----

    def cut_complete_traces(self, max_idle_s: float = 10.0,
                            force: bool = False) -> int:
        from tempo_tpu.observability.ingest_telemetry import TELEMETRY

        now = time.monotonic()
        cut = 0
        cut_ages: list[float] = []
        cut_tids: list[bytes] = []
        with self.lock:
            for tid in list(self.live):
                t = self.live[tid]
                if not force and now - t.last_append < max_idle_s:
                    continue
                obj = self.codec.to_object(t.segments)
                r = self.codec.fast_range(obj) or (0, 0)
                self.head.append(tid, obj, r[0], r[1])
                sd = t.search_data(tid)
                if sd is not None:
                    self.head_search.append(tid, sd)
                if t.first_push:
                    if t.first_push < self.head_oldest:
                        self.head_oldest = t.first_push
                    if TELEMETRY.enabled:
                        cut_ages.append(now - t.first_push)
                del self.live[tid]
                cut_tids.append(tid)
                cut += 1
            # same critical section as the head_search appends: the cut
            # traces leave the hot tier's live stage the instant they
            # become WAL-head entries — never both, never neither
            if cut_tids and LIVE_TIER.enabled:
                LIVE_TIER.mark_cut(self.tenant, cut_tids)
            obs.live_traces.set(len(self.live), tenant=self.tenant)
        for age in cut_ages:  # outside the instance lock — observe locks
            TELEMETRY.record_live_cut(age)
        return cut

    def cut_block_if_ready(self, max_block_bytes: int = 500 << 20,
                           max_block_age_s: float = 1800.0,
                           force: bool = False) -> bool:
        from tempo_tpu.observability.ingest_telemetry import TELEMETRY

        now = time.monotonic()
        with self.lock:
            if len(self.head) == 0:
                return False
            age = now - self.head_created
            if not (force or self.head.data_length >= max_block_bytes
                    or age >= max_block_age_s):
                return False
            oldest = (self.head_oldest
                      if self.head_oldest != float("inf") else None)
            self.completing.append(_Completing(
                self.head, self.head_search, cut_at=now,
                oldest_ingest=oldest))
            self._new_head()
        if TELEMETRY.enabled:
            TELEMETRY.record_block_cut(age)
        return True

    def complete_one(self, block_id: str | None = None,
                     ignore_backoff: bool = False) -> "tempopb.Trace | None":
        """Complete the oldest ELIGIBLE completing block (or the specific
        `block_id`) to the backend and clear its WAL files (reference
        handleComplete flush.go:235-281). On a backend failure the block
        is restored with a per-block exponential backoff (30s→120s cap,
        flush.go:359-389) so a flaky backend neither hot-loops one block
        nor starves its siblings — the next call skips backed-off blocks
        and completes the rest. `ignore_backoff` is the forced-flush path
        (shutdown/scale-down must not skip a backed-off block).

        The block stays IN `completing` (marked in_flight) until the
        backend write succeeds: a streaming completion can take seconds
        to minutes, and queries arriving meanwhile must still see its
        traces — the reference swaps the block out only after
        CompleteBlock returns."""
        now = time.monotonic()
        with self.lock:
            c = next((c for c in self.completing
                      if not c.in_flight
                      and (ignore_backoff or c.retry_at <= now)
                      and (block_id is None
                           or c.blk.meta.block_id == block_id)), None)
            if c is None:
                return None
            c.in_flight = True
        from tempo_tpu.observability import tracing
        from tempo_tpu.observability.ingest_telemetry import TELEMETRY

        t0 = time.perf_counter()
        with tracing.start_span("ingester.CompleteBlock",
                                tenant=self.tenant) as span:
            try:
                from tempo_tpu.robustness import FAULTS

                if FAULTS.active:
                    FAULTS.hit("flush_error")  # backend flake → backoff
                meta = self.db.complete_block(c.blk, c.search.entries())
                span.set_attributes(block_id=meta.block_id,
                                    objects=meta.total_objects)
            except Exception:
                # span.__exit__ records the propagating exception
                c.backoff_s = (self.FLUSH_BACKOFF_S if not c.backoff_s
                               else min(c.backoff_s * 2,
                                        self.FLUSH_BACKOFF_MAX_S))
                c.retry_at = time.monotonic() + c.backoff_s
                c.attempts += 1
                obs.flush_failures.inc(tenant=self.tenant)
                if TELEMETRY.enabled:
                    TELEMETRY.record_flush_retry(c.attempts)
                with self.lock:
                    c.in_flight = False
                raise
            flush_trace_id = (span.context.trace_id.hex()
                              if span.recording else None)
        done = time.monotonic()
        with self.lock:
            # atomic hand-off: queryable via `recent` (backend) the same
            # instant it leaves `completing` (WAL)
            self.completing.remove(c)
            self.recent.append((meta, done))
        c.blk.clear()
        c.search.clear()
        obs.blocks_completed.inc(tenant=self.tenant)
        obs.live_traces.set(len(self.live), tenant=self.tenant)
        if TELEMETRY.enabled:
            TELEMETRY.record_flush(
                self.tenant, meta.block_id,
                write_s=time.perf_counter() - t0,
                cut_to_flush_s=(done - c.cut_at) if c.cut_at else -1.0,
                oldest_ingest=c.oldest_ingest,
                objects=meta.total_objects, attempts=c.attempts,
                trace_id=flush_trace_id)
        return meta

    def clear_flushed(self) -> None:
        """Drop completed blocks past the query-visibility window."""
        cutoff = time.monotonic() - self.COMPLETE_BLOCK_TIMEOUT_S
        with self.lock:
            self.recent = [(m, t) for m, t in self.recent if t > cutoff]

    # ---- read path (reference instance.FindTraceByID :406) ----

    def find(self, trace_id: bytes) -> list[bytes]:
        tid = pad_trace_id(trace_id)
        partials = []
        with self.lock:
            t = self.live.get(tid)
            if t is not None and t.segments:
                partials.append(self.codec.to_object(list(t.segments)))
            heads = [self.head] + [c.blk for c in self.completing]
        for blk in heads:
            obj = blk.find(tid)
            if obj is not None:
                partials.append(obj)
        # recently completed blocks: cover the reader's blocklist-poll gap.
        # Snapshot AFTER the WAL pass — a block whose completion handed off
        # mid-iteration (its WAL find returned None on the cleared file) is
        # in `recent` by now, so the re-read closes the visibility gap.
        with self.lock:
            recent = [m for m, _ in self.recent]
        from tempo_tpu.encoding.v2 import BackendBlock

        for meta in recent:
            try:
                obj = BackendBlock(self.db.backend, meta).find_by_id(tid)
            except Exception:  # noqa: BLE001 — backend flake → partial
                continue
            if obj is not None:
                partials.append(obj)
        return partials

    # live entries walked between request-deadline reads on the legacy
    # matching loop (the StreamingSearchBlock stride twin)
    _DEADLINE_STRIDE = 256

    def search(self, req, results: SearchResults) -> None:
        from tempo_tpu.robustness import deadline as rdeadline

        if rdeadline.expired():
            # budget already spent: book partial instead of walking a
            # potentially huge live set (PR 9 contract)
            StreamingSearchBlock._book_deadline(results)
            return
        # hot tier first: the live stage kernel-scans OUTSIDE the
        # instance lock (it mirrors self.live via the push/cut hooks).
        # False = gate off or stage overflow — run the legacy walk.
        hot_live = False
        if LIVE_TIER.enabled:
            hot_live = LIVE_TIER.search(self.tenant, req, results)
        with self.lock:
            # the decode (search_data) must stay under the lock — it
            # drains the raw fragment list, which races with push
            # otherwise; the MATCHING below runs outside it
            live_sds = ([] if hot_live else
                        [sd for tid, t in self.live.items()
                         if (sd := t.search_data(tid)) is not None])
            searches = [self.head_search] + [c.search for c in self.completing]
            recent = [m for m, _ in self.recent]
        for i, sd in enumerate(live_sds):
            if i and i % self._DEADLINE_STRIDE == 0 and rdeadline.expired():
                StreamingSearchBlock._book_deadline(results)
                return
            results.metrics.inspected_traces += 1
            if search_data_matches(sd, req):
                results.add(_meta_from_sd(sd))
                if results.complete:
                    return
        for ssb in searches:
            ssb.search(req, results)
            if results.complete or results.metrics.partial:
                return
        for meta in recent:  # blocklist-poll gap, as in find()
            if rdeadline.expired():
                StreamingSearchBlock._book_deadline(results)
                return
            # once the reader's poll made this block visible, its leg of
            # the answer moved to the blocklist path — skipping it here
            # is the hot tier's eviction-on-poll contract (no double
            # scan; dedupe no longer needed for it)
            if LIVE_TIER.enabled and LIVE_TIER.poll_visible(
                    self.tenant, meta.block_id):
                continue
            try:
                self.db.search_meta(meta, req, results)
            except Exception:  # noqa: BLE001
                continue
            if results.complete:
                return

    def search_tags(self) -> set:
        tags = set()
        with self.lock:
            # bounded lock hold: decode + snapshot references only (the
            # decode drains raw fragment lists, so it cannot leave the
            # lock); the set union over every entry's kv dict runs
            # against the snapshot below, not against pushes
            sds = [sd for tid, t in self.live.items()
                   if (sd := t.search_data(tid)) is not None]
            for ssb in [self.head_search] + [c.search for c in self.completing]:
                sds.extend(ssb.entries())
        for sd in sds:
            tags.update(sd.kvs)
        for meta in self._recent_tag_blocks():
            # blocklist-poll gap, as in find()/search(): a just-completed
            # block is out of head/completing but not yet in any reader's
            # blocklist — without this sweep its tags vanish from
            # dropdowns for a full poll interval
            try:
                sp = self.db._search_block_for(meta)  # noqa: SLF001
                tags.update(sp.pages().key_dict)
            except Exception:  # noqa: BLE001 — backend flake → partial
                continue
        return tags

    # newest-first cap on the recently-completed sweep, mirroring the
    # querier's TAG_BLOCKS_LIMIT: an uncapped sweep of a busy tenant's
    # 5-minute `recent` window would decompress dozens of containers per
    # tags call and thrash the shared block cache (code-review r5)
    RECENT_TAG_BLOCKS_LIMIT = 20

    def _recent_tag_blocks(self):
        import heapq

        with self.lock:
            recent = [m for m, _ in self.recent]
        return heapq.nlargest(self.RECENT_TAG_BLOCKS_LIMIT, recent,
                              key=lambda m: m.end_time or 0)

    def search_tag_values(self, tag: str, max_bytes: int) -> set:
        vals: set[str] = set()
        size = 0
        with self.lock:
            sds = [sd for tid, t in self.live.items()
                   if (sd := t.search_data(tid)) is not None]
            for ssb in [self.head_search] + [c.search for c in self.completing]:
                sds.extend(ssb.entries())
        for sd in sds:
            for v in sd.kvs.get(tag, ()):
                if v not in vals:
                    size += len(v)
                    if size > max_bytes:
                        return vals
                    vals.add(v)
        for meta in self._recent_tag_blocks():  # blocklist-poll gap
            try:
                pages = self.db._search_block_for(meta).pages()  # noqa: SLF001
            except Exception:  # noqa: BLE001
                continue
            for s in pages.values_for_key(tag):
                if s not in vals:
                    size += len(s)
                    if size > max_bytes:
                        return vals
                    vals.add(s)
        return vals


class Ingester:
    """One ingester process: tenant instances + flush machinery + replay."""

    def __init__(self, db: TempoDB, overrides: Overrides | None = None,
                 instance_id: str = "ingester-0",
                 concurrent_flushes: int = 4):
        self.db = db
        self.overrides = overrides or Overrides()
        self.id = instance_id
        self.concurrent_flushes = concurrent_flushes
        # keyed-exclusive completion ops: a block already queued or in
        # flight is never enqueued twice, so overlapping sweeps (periodic
        # tick racing /flush or shutdown) cannot double-complete it
        # (reference pkg/flushqueues exclusivequeues.go:10-83 + flush.go:185)
        self.flush_ops = ExclusiveQueue()
        self._instances: dict[str, TenantInstance] = {}
        self._lock = threading.Lock()
        self.replayed_blocks = 0
        self._replay()

    def instance(self, tenant: str) -> TenantInstance:
        with self._lock:
            inst = self._instances.get(tenant)
            if inst is None:
                inst = self._instances[tenant] = TenantInstance(
                    tenant, self.db, self.overrides
                )
            return inst

    def tenants(self) -> list[str]:
        with self._lock:
            return sorted(self._instances)

    # ---- gRPC-facing surface (Pusher/Querier services) ----

    def push_bytes(self, tenant: str, req: tempopb.PushBytesRequest) -> None:
        inst = self.instance(tenant)
        for tid, seg, sd in zip(req.ids, req.traces, req.search_data):
            inst.push(tid, seg, sd)
        # standing queries evaluate per push micro-batch, AFTER the acks:
        # notification latency must never sit on the write path's lock
        if LIVE_TIER.enabled and LIVE_TIER.has_subscribers(tenant):
            for tid, sd in zip(req.ids, req.search_data):
                if sd:
                    LIVE_TIER.notify_push(tenant, pad_trace_id(tid), sd)

    def find_trace_by_id(self, tenant: str, trace_id: bytes) -> list[bytes]:
        with self._lock:
            inst = self._instances.get(tenant)
        return inst.find(trace_id) if inst else []

    def search(self, tenant: str, req, results: SearchResults) -> None:
        with self._lock:
            inst = self._instances.get(tenant)
        if inst:
            inst.search(req, results)

    def search_tags(self, tenant: str) -> set:
        with self._lock:
            inst = self._instances.get(tenant)
        return inst.search_tags() if inst else set()

    def search_tag_values(self, tenant: str, tag: str,
                          max_bytes: int = 1 << 20) -> set:
        with self._lock:
            inst = self._instances.get(tenant)
        return inst.search_tag_values(tag, max_bytes) if inst else set()

    # ---- flush machinery (reference ingester.loop flush.go:144-218) ----

    def sweep(self, max_idle_s: float = 10.0, force: bool = False,
              max_block_bytes: int = 500 << 20,
              max_block_age_s: float = 1800.0) -> list:
        """One flush-loop tick: cut idle traces, cut ready blocks, then
        enqueue one keyed-exclusive completion op per eligible block and
        drain the op queue with concurrent_flushes workers (reference
        flush.go:144-218). Returns completed block metas."""
        completed: list = []
        now = time.monotonic()
        for tenant in self.tenants():
            inst = self.instance(tenant)
            inst.cut_complete_traces(max_idle_s=max_idle_s, force=force)
            inst.cut_block_if_ready(max_block_bytes=max_block_bytes,
                                    max_block_age_s=max_block_age_s,
                                    force=force)
            with inst.lock:
                # force (shutdown, /flush) overrides retry backoff: a
                # scale-down must attempt every block, not strand the
                # backed-off ones in the local WAL
                eligible = [(c.blk.meta.block_id, c.retry_at)
                            for c in inst.completing
                            if force or c.retry_at <= now]
            for bid, prio in eligible:
                # False (already queued/in flight from a racing sweep) is
                # exactly the dedupe the exclusive queue exists for. The
                # op carries ITS OWN force flag: the queue is shared, so a
                # racing non-force drain may execute an op the force sweep
                # enqueued — it must still bypass the backoff.
                self.flush_ops.enqueue((tenant, bid), prio,
                                       (tenant, bid, force))
            inst.clear_flushed()

        done_lock = threading.Lock()

        def drain():
            while True:
                op = self.flush_ops.dequeue()
                if op is None:
                    return
                key, (tenant, bid, op_force) = op
                try:
                    meta = self.instance(tenant).complete_one(
                        block_id=bid, ignore_backoff=op_force)
                    if meta is not None:
                        with done_lock:
                            completed.append(meta)
                except Exception:  # noqa: BLE001 — block backed off in
                    pass           # completing; a later sweep re-enqueues
                finally:
                    self.flush_ops.done(key)

        n = min(self.concurrent_flushes, len(self.flush_ops))
        if n <= 1:
            drain()
        else:
            threads = [threading.Thread(target=drain, name=f"flush-{i}")
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        self._publish_queue_state()
        return completed

    def _publish_queue_state(self) -> None:
        """Post-drain backlog gauges: per-tenant flush-queue depth and
        the age of the oldest trace not yet flushed (head + completing)
        — the white-box 'how far behind is this ingester' signal."""
        from tempo_tpu.observability.ingest_telemetry import TELEMETRY

        if not TELEMETRY.enabled:
            return
        now = time.monotonic()
        for tenant in self.tenants():
            inst = self.instance(tenant)
            with inst.lock:
                qlen = len(inst.completing)
                # replayed blocks carry no push stamp (oldest_ingest is
                # None) — fall back to their enqueue time so a wedged
                # post-restart backlog ages instead of reading 0
                candidates = [c.oldest_ingest if c.oldest_ingest is not None
                              else c.cut_at
                              for c in inst.completing if c.cut_at]
                if len(inst.head) and inst.head_oldest != float("inf"):
                    candidates.append(inst.head_oldest)
                live_oldest = [t.first_push
                               for t in inst.live.values() if t.first_push]
                if live_oldest:
                    candidates.append(min(live_oldest))
            oldest = min(candidates, default=None)
            TELEMETRY.set_queue_state(
                tenant, qlen, (now - oldest) if oldest is not None else 0.0)

    def flush_all(self, settle_timeout_s: float = 60.0) -> list:
        """Graceful shutdown / scale-down: force everything to the backend
        (reference /shutdown handler flush.go:91-115). Loops until no
        completing blocks remain. A pass that completes nothing is only
        counted as stalled after all in-flight completions have settled —
        a racing periodic sweep's drain thread may hold the op for a
        streaming completion that takes minutes, during which our own
        passes are no-ops by design (ExclusiveQueue dedupe). Two settled
        no-progress passes mean the backend is genuinely down; then we
        raise FlushIncompleteError so the caller cannot mistake a partial
        flush for success and delete the node's WAL disk.

        settle_timeout_s bounds the wait for RACING in-flight completions
        (a periodic sweep's drain thread holding the op) so they cannot
        pin shutdown indefinitely; a false stall only raises — the WAL
        stays on disk and the racing completion, if any, still finishes.
        It does NOT bound the backend writes our own passes issue: those
        rely on the backend transport's request timeouts (a local/memory
        backend cannot blackhole; cloud backends go through the
        timeout-carrying instrumented transport)."""
        completed: list = []
        stalled = 0
        while stalled < 2:
            before = len(completed)
            completed += self.sweep(force=True)
            if not self._blocks_left():
                return completed
            if len(completed) == before:
                self._wait_inflight_settled(settle_timeout_s)
                if not self._blocks_left():
                    return completed
                stalled += 1
            else:
                stalled = 0
        # raise only — callers own the logging (double error lines per
        # ingester otherwise)
        raise FlushIncompleteError(left_behind=self._blocks_left(),
                                   completed=completed)

    def _blocks_left(self) -> int:
        with self._lock:
            insts = list(self._instances.values())
        return sum(len(i.completing) for i in insts)

    def _wait_inflight_settled(self, timeout_s: float) -> None:
        """Block until no completion op is executing anywhere — neither a
        block marked in_flight nor a claimed-but-unreleased flush-op key
        (the window between dequeue() and complete_one picking the
        block)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                insts = list(self._instances.values())
            busy = self.flush_ops.in_flight() > 0 or any(
                c.in_flight for i in insts for c in i.completing)
            if not busy:
                return
            time.sleep(0.05)

    # ---- replay (reference replayWal ingester.go:327-416) ----

    def _replay(self) -> None:
        from tempo_tpu.observability import get_logger
        from tempo_tpu.observability.ingest_telemetry import TELEMETRY

        blocks, _removed = self.db.wal.replay_all()
        stats = self.db.wal.last_replay or {}
        # replay happens exactly once per process start and gates
        # readiness — log it always, export it when telemetry is on, so
        # a 90-second restart is attributable to the N GB it re-scanned
        if blocks or stats.get("removed_files"):
            get_logger("tempo_tpu.ingester").info(
                "wal replay: %d block(s), %d bytes, %d corrupt record(s) "
                "dropped, %d file(s) removed in %.3fs",
                stats.get("blocks", 0), stats.get("bytes", 0),
                stats.get("corrupt_records", 0),
                stats.get("removed_files", 0),
                stats.get("duration_s", 0.0))
        if TELEMETRY.enabled:
            TELEMETRY.record_wal_replay(
                stats.get("duration_s", 0.0), stats.get("blocks", 0),
                stats.get("bytes", 0), stats.get("corrupt_records", 0))
        for blk in blocks:
            tenant = blk.meta.tenant_id
            inst = self.instance(tenant)
            import os

            spath = blk.path + ".search"
            if os.path.exists(spath):
                ssb = StreamingSearchBlock.rescan(spath)
            else:
                ssb = StreamingSearchBlock(spath)
            # replayed head blocks go straight to completing: they will be
            # completed by the next sweep (reference re-enqueues completion
            # ops for replayed blocks). cut_at stamps NOW — the traces'
            # real push times predate this process, so the queue-age
            # gauge counts from restart (it must read nonzero and GROW
            # while a backlogged restart can't flush, not report 0 =
            # "fully flushed"); oldest_ingest stays None so the
            # push_to_searchable histogram is never fed restart-relative
            # values
            inst.completing.append(_Completing(blk, ssb,
                                               cut_at=time.monotonic()))
            self.replayed_blocks += 1
