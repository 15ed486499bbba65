"""Querier: stateless read worker.

Role-equivalent to the reference's modules/querier (querier.go:60-452):
trace-by-ID queries the ingester replica set AND the backend blocklist,
combining partials; SearchRecent fans out to ingesters; SearchBlock
executes one frontend-sharded job against the TPU engine; tag queries
aggregate ingester + block dictionaries under byte limits.
"""

from __future__ import annotations

import contextvars

from tempo_tpu import tempopb
from tempo_tpu.db import TempoDB
from tempo_tpu.model.codec import codec_for, CURRENT_ENCODING
from tempo_tpu.model.matches import trace_search_metadata
from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import tracing
from tempo_tpu.robustness import FAULTS, deadline as rdeadline
from tempo_tpu.search import SearchResults
from tempo_tpu.utils.hashing import token_for
from tempo_tpu.utils.ids import pad_trace_id
from .overrides import Overrides
from .ring import Ring

def _ctx_submit(pool, fn, *args):
    """Submit to the replica pool UNDER the submitter's contextvars:
    the request's current span and deadline follow the read onto the
    worker, so spans opened there parent into the request's trace and
    a breaker fault booked mid-fanout carries the offending trace id
    into its flight-recorder bundle instead of an anonymous None."""
    ctx = contextvars.copy_context()
    return pool.submit(ctx.run, fn, *args)


QUERY_MODE_INGESTERS = "ingesters"
QUERY_MODE_BLOCKS = "blocks"
QUERY_MODE_ALL = "all"


class Querier:
    # blocks consulted by the tag endpoints' backend leg, newest first.
    # The reference answers tags from INGESTERS only (querier.go); the
    # block leg here is a richer answer but must not stage a 10K-block
    # corpus through the 64-entry container LRU per tags call
    TAG_BLOCKS_LIMIT = 100

    def __init__(self, db: TempoDB, ring: Ring, ingesters: dict,
                 overrides: Overrides | None = None,
                 external_endpoints: list | None = None,
                 prefer_self: int = 10,
                 external_hedge_after_s: float = 4.0,
                 fanout_workers: int | None = None):
        """ingesters: instance id → object with find_trace_by_id/search/
        instance() (in-process Ingester or gRPC stub).

        external_endpoints: serverless search-worker URLs; SearchBlock jobs
        overflow to them when more than `prefer_self` jobs run locally
        (reference querier.go:397-452: hedged external search with a
        prefer-self semaphore)."""
        import concurrent.futures
        import threading

        self.db = db
        self.ring = ring
        self.ingesters = ingesters
        self.overrides = overrides or Overrides()
        self.external_endpoints = list(external_endpoints or [])
        self._prefer_self = threading.Semaphore(prefer_self)
        self.external_hedge_after_s = external_hedge_after_s
        self._rr = 0
        # replica fan-out pool: ingester reads go out CONCURRENTLY so one
        # slow replica costs max(replicas), not sum (reference
        # querier.go:252-276 errgroup). Sized for concurrent REQUESTS ×
        # replicas because early-quit stragglers pin their thread until
        # the RPC completes — a pool at ~replica count would head-of-line
        # block independent requests behind one slow ingester
        self._fanout_fixed = fanout_workers is not None
        self._fanout_size = fanout_workers or 32
        self._fanout_lock = threading.Lock()
        self._fanout = concurrent.futures.ThreadPoolExecutor(
            max_workers=self._fanout_size,
            thread_name_prefix="replica-fanout")

    def _fanout_pool(self):
        """The replica pool, re-sized as gossip discovers ingesters: the
        dict is usually EMPTY at construction in microservices mode, so
        a build-time snapshot would lock in the floor and reintroduce
        head-of-line blocking at scale. Growth swaps in a bigger
        executor; the old one drains its in-flight tasks and exits."""
        import concurrent.futures

        if self._fanout_fixed:
            return self._fanout
        try:
            n = len(self.ingesters)
        except Exception:  # noqa: BLE001 — dynamic client dicts
            n = 0
        want = max(32, 8 * max(1, n))
        if want > self._fanout_size:
            with self._fanout_lock:
                if want > self._fanout_size:
                    # deliberately NOT shutting the old pool down: a
                    # concurrent request captured it before the swap and
                    # its next submit would raise "cannot schedule new
                    # futures after shutdown" — dropping the reference
                    # lets in-flight work finish and idle threads die
                    # with the executor at GC
                    self._fanout = concurrent.futures.ThreadPoolExecutor(
                        max_workers=want,
                        thread_name_prefix="replica-fanout")
                    self._fanout_size = want
        return self._fanout

    # ---- trace by id (reference querier.go:171-249) ----

    def find_trace_by_id(self, tenant: str, trace_id: bytes,
                         block_start: str = "", block_end: str = "",
                         mode: str = QUERY_MODE_ALL) -> tempopb.TraceByIDResponse:
        tid = pad_trace_id(trace_id)
        partials: list[bytes] = []
        failed = 0

        if mode in (QUERY_MODE_INGESTERS, QUERY_MODE_ALL):
            import concurrent.futures

            replicas = self.ring.get(token_for(tenant, tid))
            futs = []
            for iid in replicas:
                ing = self.ingesters.get(iid)
                if ing is None:
                    failed += 1
                    obs.partial_results.inc(reason="replica")
                    continue
                futs.append(_ctx_submit(self._fanout_pool(),
                                        ing.find_trace_by_id, tenant, tid))
            try:
                # bounded by the request deadline, like search_recent:
                # a replica wedged behind a dead backend must not hold
                # the lookup hostage
                for f in concurrent.futures.as_completed(
                        futs, timeout=rdeadline.remaining()):
                    try:
                        partials.extend(f.result())
                    except Exception:  # noqa: BLE001 — replica → partial
                        failed += 1
                        obs.partial_results.inc(reason="replica")
            except concurrent.futures.TimeoutError:
                undone = sum(1 for f in futs if not f.done())
                failed += undone
                obs.partial_results.inc(undone, reason="deadline")

        if mode in (QUERY_MODE_BLOCKS, QUERY_MODE_ALL):
            obj, block_failed = self.db.find_trace_by_id(
                tenant, tid, block_start, block_end
            )
            failed += block_failed
            if block_failed:
                obs.partial_results.inc(block_failed, reason="backend")
            if obj is not None:
                partials.append(obj)

        resp = tempopb.TraceByIDResponse()
        resp.metrics.failed_blocks = failed
        if partials:
            codec = codec_for(CURRENT_ENCODING)
            obj = partials[0] if len(partials) == 1 else codec.combine(*partials)
            resp.trace.CopyFrom(codec.prepare_for_read(obj))
        return resp

    # ---- search (reference SearchRecent :278, SearchBlock :397) ----

    def search_recent(self, tenant: str, req: tempopb.SearchRequest) -> tempopb.SearchResponse:
        """Concurrent fan-out over the ingester replica set with merge +
        early quit: latency is the slowest replica still NEEDED, not the
        sum of all (reference querier.go:252-276). A failed replica
        counts as failed_blocks — an operator must be able to tell
        "pruned" from "broken" — and the merge stops once the limit is
        satisfied (stragglers complete in the pool, their answers moot)."""
        import concurrent.futures

        results = SearchResults.for_request(req)
        ings = list(self.ingesters.values())
        if not ings:
            return results.response()

        def one(ing):
            if FAULTS.active:
                FAULTS.hit("replica_error")
            local = SearchResults.for_request(req)
            ing.search(tenant, req, local)
            return local.response()

        pool = self._fanout_pool()
        futs = [_ctx_submit(pool, one, ing) for ing in ings]
        try:
            # bounded by the request deadline: a replica stuck behind a
            # dead device must not hold the whole answer hostage —
            # stragglers complete in the pool, their answers moot
            for f in concurrent.futures.as_completed(
                    futs, timeout=rdeadline.remaining()):
                try:
                    results.merge_response(f.result())
                except Exception:  # noqa: BLE001 — replica failure → partial
                    results.metrics.failed_blocks += 1
                    results.metrics.partial = True
                    obs.partial_results.inc(reason="replica")
                    continue
                if results.complete:
                    break
        except concurrent.futures.TimeoutError:
            undone = sum(1 for f in futs if not f.done())
            results.metrics.failed_blocks += undone
            results.metrics.partial = True
            obs.partial_results.inc(undone, reason="deadline")
        return results.response()

    def search_block(self, req: tempopb.SearchBlockRequest) -> tempopb.SearchResponse:
        if self.external_endpoints:
            if self._prefer_self.acquire(blocking=False):
                try:
                    return self.db.search_block(req).response()
                finally:
                    self._prefer_self.release()
            return self._search_external(req)
        return self.db.search_block(req).response()

    def search_blocks(self, req: tempopb.SearchBlocksRequest) -> tempopb.SearchResponse:
        """Batched job execution: one kernel dispatch per geometry group
        — and under concurrency, FEWER: concurrent search_blocks calls
        (several frontend requests, several tenants' dashboards) route
        into the shared BlockBatcher, whose QueryCoalescer fuses
        dispatches that land on the same staged batch within the
        coalescing window into one multi-query kernel launch. The
        querier adds no serialization of its own — each call runs on its
        caller's worker thread so peers can actually meet in the window.
        With serverless endpoints configured the batch degrades to
        singular jobs so overflow can proxy out (the external workers
        speak SearchBlockRequest); that path bypasses batching AND
        coalescing."""
        with tracing.start_span(
                "querier.SearchBlocks", tenant=req.tenant_id,
                jobs=len(req.jobs)) as span:
            resp = self._search_blocks(req)
            # dispatch counts live in scan_dispatches{mode=batched|
            # coalesced}, not here: the batcher's last-search scratch is
            # shared across concurrent searches and would attribute
            # another request's dispatches to this span
            span.set_attributes(
                inspected_blocks=resp.metrics.inspected_blocks)
            return resp

    def _search_blocks(self, req: tempopb.SearchBlocksRequest) -> tempopb.SearchResponse:
        if self.external_endpoints:
            from tempo_tpu.search import SearchResults

            results = SearchResults.for_request(req.search_req)
            for j in req.jobs:
                one = tempopb.SearchBlockRequest()
                one.search_req.CopyFrom(req.search_req)
                one.tenant_id = req.tenant_id
                one.block_id = j.block_id
                one.start_page = j.start_page
                one.pages_to_search = j.pages_to_search
                one.encoding = j.encoding
                one.version = j.version
                one.data_encoding = j.data_encoding
                one.start_time = j.start_time
                one.end_time = j.end_time
                results.merge_response(self.search_block(one))
                if results.complete:
                    break
            return results.response()
        return self.db.search_blocks(req).response()

    def _search_external(self, req: tempopb.SearchBlockRequest) -> tempopb.SearchResponse:
        """Proxy one job to a serverless search worker, hedged (reference
        searchExternalEndpoint: up to 2 extra hedges)."""
        import urllib.request

        from tempo_tpu.db.hedge import hedged_call

        body = req.SerializeToString()
        endpoint = self.external_endpoints[self._rr % len(self.external_endpoints)]
        self._rr += 1

        def call():
            r = urllib.request.Request(
                endpoint.rstrip("/") + "/search-block", data=body,
                headers={"Content-Type": "application/protobuf"},
            )
            with urllib.request.urlopen(r, timeout=30) as resp:
                out = tempopb.SearchResponse()
                out.ParseFromString(resp.read())
                return out

        return hedged_call(call, hedge_after_s=self.external_hedge_after_s,
                           max_hedges=2)

    # ---- tags ----

    def _tag_blocks(self, tenant: str):
        """Newest blocks first, capped: recent blocks carry the live tag
        universe; a full-corpus container sweep per tags call would
        thrash the staging LRU at scale."""
        import heapq

        return heapq.nlargest(self.TAG_BLOCKS_LIMIT,
                              self.db.blocklist.metas(tenant),
                              key=lambda m: m.end_time or 0)

    def search_tags(self, tenant: str) -> tempopb.SearchTagsResponse:
        tags: set[str] = set()
        for ing in self.ingesters.values():
            try:
                tags.update(ing.search_tags(tenant))
            except Exception:  # noqa: BLE001 — replica failure → partial tags
                obs.partial_results.inc(reason="replica")
                continue
        for m in self._tag_blocks(tenant):
            try:
                pages = self.db._search_block_for(m).pages()  # noqa: SLF001
                tags.update(pages.key_dict)
            except Exception:  # noqa: BLE001 — blocks without search data
                obs.partial_results.inc(reason="backend")
                continue
        resp = tempopb.SearchTagsResponse()
        resp.tag_names.extend(sorted(tags))
        return resp

    def search_tag_values(self, tenant: str, tag: str) -> tempopb.SearchTagValuesResponse:
        lim = self.overrides.limits(tenant)
        vals: set[str] = set()
        size = 0
        for ing in self.ingesters.values():
            try:
                vals.update(ing.search_tag_values(
                    tenant, tag, lim.max_bytes_per_tag_values))
            except Exception:  # noqa: BLE001 — replica failure → partial values
                obs.partial_results.inc(reason="replica")
                continue
        budget_hit = False
        for m in self._tag_blocks(tenant):
            if budget_hit:
                # a tripped byte budget must stop the whole sweep, not
                # just the current block — each further block costs a
                # backend read + decompress + staging for nothing
                break
            try:
                pages = self.db._search_block_for(m).pages()  # noqa: SLF001
            except Exception:  # noqa: BLE001
                obs.partial_results.inc(reason="backend")
                continue
            for s in pages.values_for_key(tag):
                if s not in vals:
                    size += len(s)
                    if size > lim.max_bytes_per_tag_values:
                        budget_hit = True
                        break
                    vals.add(s)
        resp = tempopb.SearchTagValuesResponse()
        resp.tag_values.extend(sorted(vals))
        return resp
