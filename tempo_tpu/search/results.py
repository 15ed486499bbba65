"""Result collection: dedupe, limit, metrics.

Role-equivalent to the reference's search.Results channel funnel
(tempodb/search/results.go:14-141) and util.go result combination — here a
simple synchronous collector (the device kernel already reduces per block;
cross-block merge is cheap host work), carrying the same SearchMetrics
counters the bench harness compares (inspectedTraces/Bytes/Blocks,
skippedBlocks)."""

from __future__ import annotations

from tempo_tpu import tempopb
from tempo_tpu.observability import tracing


class SearchResults:
    def __init__(self, limit: int = 20, no_quit: bool = False):
        self.limit = limit
        # no_quit suppresses `complete` so fan-out never early-stops —
        # set by the exhaustive debug tag (reference's secret tag keeps the
        # scan from quitting by rejecting everything; here the flag is
        # explicit so real matches still come back)
        self.no_quit = no_quit
        self._by_id: dict[str, tempopb.TraceSearchMetadata] = {}
        self.metrics = tempopb.SearchMetrics()
        # explain breakdowns carried by merged sub-responses
        # (metrics.query_stats_json, present only under the explain
        # opt-in) — the frontend folds these into its request-level
        # QueryStats instead of concatenating opaque strings
        self.explain_parts: list[dict] = []
        # ?agg= aggregate payload (search/analytics.py agg_response
        # shape), merged exactly across groups and sub-responses —
        # integer counts, so fan-in order never changes the answer
        self.agg: dict | None = None

    @classmethod
    def for_request(cls, req) -> "SearchResults":
        from .analytics import agg_requested
        from .pipeline import is_exhaustive

        # an aggregation must see every contributing group: the limit
        # early-quit would freeze the aggregate at whichever groups
        # happened to drain first (cache-residency-dependent), breaking
        # the cross-route byte-identity the ?agg= contract promises
        return cls(limit=req.limit or 20,
                   no_quit=is_exhaustive(req) or agg_requested(req))

    def add_agg(self, series: dict) -> None:
        """Fold one group's decoded agg series in (AggStage.decode) —
        called per drained dispatch, device and host routes alike."""
        from .analytics import agg_response, merge_agg

        self.agg = merge_agg(self.agg, agg_response(series))

    def add(self, meta: tempopb.TraceSearchMetadata) -> None:
        prev = self._by_id.get(meta.trace_id)
        if prev is None:
            self._by_id[meta.trace_id] = meta
        else:
            # keep the earlier start / longer duration (combination rule of
            # reference util.go:27-62)
            if meta.start_time_unix_nano and (
                not prev.start_time_unix_nano
                or meta.start_time_unix_nano < prev.start_time_unix_nano
            ):
                prev.start_time_unix_nano = meta.start_time_unix_nano
            prev.duration_ms = max(prev.duration_ms, meta.duration_ms)
            if not prev.root_service_name:
                prev.root_service_name = meta.root_service_name
                prev.root_trace_name = meta.root_trace_name

    def merge_response(self, resp: tempopb.SearchResponse) -> None:
        """Fold a sub-request's response in: dedupe traces, sum metrics
        (the frontend/querier merge, reference searchsharding.go:70-124)."""
        for t in resp.traces:
            self.add(t)
        m = self.metrics
        m.inspected_traces += resp.metrics.inspected_traces
        m.inspected_bytes += resp.metrics.inspected_bytes
        m.inspected_blocks += resp.metrics.inspected_blocks
        m.skipped_blocks += resp.metrics.skipped_blocks
        m.truncated_entries += resp.metrics.truncated_entries
        m.failed_blocks += resp.metrics.failed_blocks
        # per-query accounting fields sum like the counters above —
        # this is how device-seconds attribution crosses the
        # frontend/querier process boundary
        m.device_seconds += resp.metrics.device_seconds
        m.inspected_bytes_device += resp.metrics.inspected_bytes_device
        # degraded-ness is sticky across the merge: ONE partial
        # sub-response makes the whole answer partial — a degraded
        # answer must never be indistinguishable from a complete one
        if resp.metrics.partial:
            m.partial = True
        if resp.metrics.query_stats_json:
            import json

            try:
                self.explain_parts.append(
                    json.loads(resp.metrics.query_stats_json))
            except ValueError:
                pass  # a malformed part never fails a merge
        if resp.metrics.agg_json:
            import json

            from .analytics import merge_agg

            # aggregating searches only: no flat search writes the span
            with tracing.start_span("results.merge_agg"):
                try:
                    self.agg = merge_agg(
                        self.agg, json.loads(resp.metrics.agg_json))
                except ValueError:
                    pass  # a malformed part never fails a merge

    @property
    def n_results(self) -> int:
        # deliberately NOT __len__: callers use `results or for_request`
        # to default a None argument, and a falsy empty collector would
        # silently swap in a fresh object there
        return len(self._by_id)

    @property
    def complete(self) -> bool:
        return not self.no_quit and len(self._by_id) >= self.limit

    def response(self) -> tempopb.SearchResponse:
        resp = tempopb.SearchResponse()
        # tie-break equal start times by trace id: insertion order here
        # depends on sub-result COMPLETION order (frontend shard
        # threads, host-routed groups answering inline while device
        # groups drain), and the reference sorts by start time only —
        # a deterministic secondary key makes the response (including
        # the limit cutoff) independent of where each group was served,
        # which is what lets owner-routed/breaker fallback paths assert
        # byte-identity
        metas = sorted(
            self._by_id.values(),
            key=lambda m: (-m.start_time_unix_nano, m.trace_id),
        )[: self.limit]
        resp.traces.extend(metas)
        resp.metrics.CopyFrom(self.metrics)
        if self.agg is not None:
            import json

            # sort_keys: the series dict's insertion order depends on
            # which group drained first — canonical JSON keeps the
            # byte-identity assertions across dispatch routes honest
            resp.metrics.agg_json = json.dumps(self.agg, sort_keys=True)
        return resp
