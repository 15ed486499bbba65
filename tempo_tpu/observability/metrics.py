"""Prometheus-style metrics registry.

Role-equivalent to the reference's promauto counters/gauges/histograms
registered at var-init in every component with `tempo_`/`tempodb_`
namespaces (SURVEY.md §5 observability), exposed in text format at
/metrics. Labels are per-series (cardinality-aware: the label set lives
in the series key).

Exemplars ("tempo traces tempo", closed loop): a Histogram observation
made while a SAMPLED self-trace span is active records that span's
trace_id against the bucket the value fell in. ``/metrics`` negotiates
OpenMetrics via ``Accept`` (api/http.py) and ``expose(openmetrics=True)``
emits the exemplars per the OpenMetrics 1.0 text format — latency
buckets become clickable into the self-traces that produced them. The
classic Prometheus text format (0.0.4) is byte-identical to before.
"""

from __future__ import annotations

import os
import threading
import time
from bisect import bisect_left

OPENMETRICS_CONTENT_TYPE = (
    "application/openmetrics-text; version=1.0.0; charset=utf-8")
PROM_CONTENT_TYPE = "text/plain; version=0.0.4"

# label values arrive as strings or numbers (mode="mesh", le=0.5); the
# series key is the sorted (name, value) tuple
LabelValue = str | int | float
SeriesKey = tuple[tuple[str, LabelValue], ...]
# (metric_name, series_key, value) — the remote-write drain format
Sample = tuple[str, SeriesKey, float]
# (trace_id_hex, observed value, unix_ts) — one bucket exemplar
Exemplar = tuple[str, float, float]


def _exemplar_ref() -> str | None:
    """trace_id (hex) of the active sampled self-trace span, or None.
    Imported lazily: tracing imports this module at load for its own
    counters; the call path here only runs post-import."""
    from . import tracing

    s = tracing.current_span()
    if s.recording and s.context.sampled:
        return s.context.trace_id.hex()
    return None


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help_: str = "",
                 registry: "Registry | None" = None):
        self.name = name
        self.help = help_
        self._series: dict[SeriesKey, float] = {}
        self._lock = threading.Lock()
        (registry or REGISTRY)._register(self)

    def _key(self, labels: dict[str, LabelValue] | None) -> SeriesKey:
        return tuple(sorted((labels or {}).items()))

    def _om_base(self) -> str:
        """OpenMetrics metric-family name: counters are named WITHOUT the
        `_total` suffix in HELP/TYPE lines (the suffix belongs to the
        sample), everything else is unchanged."""
        if self.kind == "counter" and self.name.endswith("_total"):
            return self.name[: -len("_total")]
        return self.name

    def expose(self, openmetrics: bool = False) -> str:
        name = self._om_base() if openmetrics else self.name
        lines = [f"# HELP {name} {self.help}",
                 f"# TYPE {name} {self.kind}"]
        with self._lock:
            for key, val in sorted(self._series.items()):
                lbl = ",".join(f'{k}="{v}"' for k, v in key)
                lines.append(f"{self.name}{{{lbl}}} {val}" if lbl
                             else f"{self.name} {val}")
        return "\n".join(lines)

    def samples(self) -> list[Sample]:
        """[(metric_name, ((label, value), ...), float)] — the
        remote-write drain format."""
        with self._lock:
            return [(self.name, key, val)
                    for key, val in sorted(self._series.items())]


class Counter(_Metric):
    kind = "counter"

    def __init__(self, name: str, help_: str = "",
                 registry: "Registry | None" = None, read=None):
        """`read`: for a counter the operating system keeps for the
        process (its CPU clocks): the unlabelled series is `read()`,
        taken whenever the counter is rendered, sampled or asked for;
        nothing writes it, so it costs no request anything."""
        super().__init__(name, help_, registry)
        self._read = read

    def _refresh(self) -> None:
        if self._read is not None:
            v = self._read()
            with self._lock:
                self._series[()] = v

    def expose(self, openmetrics: bool = False) -> str:
        self._refresh()
        return super().expose(openmetrics)

    def samples(self) -> list[Sample]:
        self._refresh()
        return super().samples()

    def inc(self, n: float = 1, **labels: LabelValue) -> None:
        k = self._key(labels)
        with self._lock:
            self._series[k] = self._series.get(k, 0) + n

    def labels(self, **labels: LabelValue) -> "_BoundCounter":
        """Precomputed-key handle for per-span hot paths: the sorted
        label-tuple build per inc() was measurable on the ingest ack
        path (profiled r5) — cache the handle, pay it once."""
        return _BoundCounter(self, self._key(labels))

    def value(self, **labels: LabelValue) -> float:
        """Sum of the series that carry every given label with the given
        value: the one series when all its labels are given, and still
        the right count for a reader that names fewer labels than the
        writers set (`scan_dispatches.value(mode="batched")` over every
        `shards`)."""
        self._refresh()
        want = set(labels.items())
        # locked like every writer: a bare dict read races resize-in-
        # progress under free-threading and misses published updates
        with self._lock:
            return sum(v for k, v in self._series.items()
                       if want.issubset(k))


class _BoundCounter:
    __slots__ = ("_m", "_k")

    def __init__(self, m: Counter, k: SeriesKey):
        self._m, self._k = m, k

    def inc(self, n: float = 1) -> None:
        m = self._m
        with m._lock:
            m._series[self._k] = m._series.get(self._k, 0) + n


class Gauge(_Metric):
    kind = "gauge"

    def set(self, v: float, **labels: LabelValue) -> None:
        with self._lock:
            self._series[self._key(labels)] = v

    def add(self, n: float, **labels: LabelValue) -> None:
        """Delta update (negative to decrement) — for gauges tracking
        in-flight counts with no single owner to re-derive them from
        (e.g. SSE response bodies draining on server writer threads)."""
        k = self._key(labels)
        with self._lock:
            self._series[k] = self._series.get(k, 0) + n

    def remove(self, **labels: LabelValue) -> None:
        """Drop one labeled series. A per-tenant gauge whose tenant
        vanished must stop exporting its last value — a frozen
        'freshness: 2.1s' for a tenant with no searchable data left is
        worse than no series at all."""
        with self._lock:
            self._series.pop(self._key(labels), None)

    def value(self, **labels: LabelValue) -> float:
        with self._lock:
            return self._series.get(self._key(labels), 0)


class Histogram(_Metric):
    kind = "histogram"
    DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10)

    def __init__(self, name: str, help_: str = "",
                 buckets: tuple[float, ...] | None = None,
                 registry: "Registry | None" = None):
        super().__init__(name, help_, registry)
        self.buckets: tuple[float, ...] = tuple(
            buckets or self.DEFAULT_BUCKETS)
        self._counts: dict[SeriesKey, list[int]] = {}
        self._sums: dict[SeriesKey, float] = {}
        # series key -> {bin index: (trace_id_hex, value, unix_ts)}:
        # the newest sampled-span observation per bucket — OpenMetrics
        # exemplars linking latency buckets to self-traces
        self._exemplars: dict[SeriesKey, dict[int, Exemplar]] = {}

    def observe(self, v: float, **labels: LabelValue) -> None:
        self._observe_key(self._key(labels), v)

    def _observe_key(self, k: SeriesKey, v: float) -> None:
        # counts holds per-BIN tallies (bin i = first bucket >= v, last =
        # +Inf only); expose()/samples() cumsum into the prometheus
        # cumulative-le form. One bisect + one increment beats the old
        # O(buckets) cumulative walk on the per-span ingest path.
        i = bisect_left(self.buckets, v)
        ex = _exemplar_ref()  # before the lock: reads a contextvar only
        with self._lock:
            counts = self._counts.get(k)
            if counts is None:
                counts = self._counts[k] = [0] * (len(self.buckets) + 1)
            counts[i] += 1
            self._sums[k] = self._sums.get(k, 0) + v
            if ex is not None:
                self._exemplars.setdefault(k, {})[i] = (ex, v, time.time())

    def observe_bulk(self, bins: list[int], vals: list[float],
                     **labels: LabelValue) -> None:
        self._observe_bulk_key(self._key(labels), bins, vals)

    def _observe_bulk_key(self, k: SeriesKey, bins: list[int],
                          vals: list[float]) -> None:
        # batched drain for the device analytics path: per-bin tallies
        # arrive pre-counted, and the float sum folds sequentially in
        # row order under one lock hold — the resulting series is
        # byte-identical to the same values through observe() one by one
        ex = _exemplar_ref()
        with self._lock:
            counts = self._counts.get(k)
            if counts is None:
                counts = self._counts[k] = [0] * (len(self.buckets) + 1)
            for i, n in enumerate(bins):
                counts[i] += n
            s = self._sums.get(k, 0)
            for v in vals:
                s = s + v
            self._sums[k] = s
            if ex is not None and vals:
                exs = self._exemplars.setdefault(k, {})
                for v in vals:
                    exs[bisect_left(self.buckets, v)] = (ex, v,
                                                         time.time())

    def labels(self, **labels: LabelValue) -> "_BoundHistogram":
        return _BoundHistogram(self, self._key(labels))

    def time(self, **labels: LabelValue) -> "_Timer":
        return _Timer(self, labels)

    @staticmethod
    def _exemplar_suffix(ex: Exemplar | None) -> str:
        """OpenMetrics exemplar: ` # {labels} value timestamp`."""
        if ex is None:
            return ""
        trace_id, value, ts = ex
        return f' # {{trace_id="{trace_id}"}} {value} {round(ts, 3)}'

    def expose(self, openmetrics: bool = False) -> str:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} histogram"]
        with self._lock:
            for key, counts in sorted(self._counts.items()):
                base = dict(key)
                exs = self._exemplars.get(key, {}) if openmetrics else {}
                cum = 0
                for i, b in enumerate(self.buckets):
                    cum += counts[i]
                    # OpenMetrics requires float-formatted thresholds
                    le = float(b) if openmetrics else b
                    lbl = ",".join(f'{k}="{v}"' for k, v in
                                   sorted({**base, "le": le}.items()))
                    lines.append(f"{self.name}_bucket{{{lbl}}} {cum}"
                                 + self._exemplar_suffix(exs.get(i)))
                total = cum + counts[-1]
                lbl = ",".join(f'{k}="{v}"' for k, v in
                               sorted({**base, "le": "+Inf"}.items()))
                lines.append(f"{self.name}_bucket{{{lbl}}} {total}"
                             + self._exemplar_suffix(
                                 exs.get(len(self.buckets))))
                blbl = ",".join(f'{k}="{v}"' for k, v in key)
                suffix = f"{{{blbl}}}" if blbl else ""
                lines.append(f"{self.name}_sum{suffix} {self._sums.get(key, 0)}")
                lines.append(f"{self.name}_count{suffix} {total}")
        return "\n".join(lines)

    def samples(self) -> list[Sample]:
        out: list[Sample] = []
        with self._lock:
            for key, counts in sorted(self._counts.items()):
                base = dict(key)
                cum = 0
                for i, b in enumerate(self.buckets):
                    cum += counts[i]
                    out.append((f"{self.name}_bucket",
                                tuple(sorted({**base, "le": str(b)}.items())),
                                cum))
                total = cum + counts[-1]
                out.append((f"{self.name}_bucket",
                            tuple(sorted({**base, "le": "+Inf"}.items())),
                            total))
                out.append((f"{self.name}_sum", key, self._sums.get(key, 0)))
                out.append((f"{self.name}_count", key, total))
        return out


class _BoundHistogram:
    __slots__ = ("_m", "_k")

    def __init__(self, m: Histogram, k: SeriesKey):
        self._m, self._k = m, k

    def observe(self, v: float) -> None:
        self._m._observe_key(self._k, v)

    def observe_bulk(self, bins: list[int], vals: list[float]) -> None:
        self._m._observe_bulk_key(self._k, bins, vals)


class _Timer:
    def __init__(self, hist: Histogram, labels: dict[str, LabelValue]):
        self.hist = hist
        self.labels = labels
        self.t0 = 0.0

    def __enter__(self) -> "_Timer":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: object) -> None:
        self.hist.observe(time.perf_counter() - self.t0, **self.labels)


class Registry:
    def __init__(self) -> None:
        self._metrics: dict[str, _Metric] = {}
        self._lock = threading.Lock()

    def _register(self, m: _Metric) -> None:
        with self._lock:
            if m.name in self._metrics:
                raise ValueError(f"metric {m.name} already registered")
            self._metrics[m.name] = m

    def get(self, name: str) -> _Metric | None:
        return self._metrics.get(name)

    def expose(self, openmetrics: bool = False) -> str:
        with self._lock:
            metrics = list(self._metrics.values())
        body = "\n".join(m.expose(openmetrics) for m in metrics) + "\n"
        if openmetrics:
            body += "# EOF\n"
        return body

    def samples(self) -> list[Sample]:
        with self._lock:
            metrics = list(self._metrics.values())
        out: list[Sample] = []
        for m in metrics:
            out.extend(m.samples())
        return out


REGISTRY = Registry()

# core framework metrics (registered once, labelled per tenant/status)
ingest_spans = Counter("tempo_distributor_spans_received_total",
                       "spans received by the distributor")
ingest_bytes = Counter("tempo_distributor_bytes_received_total",
                       "bytes received by the distributor")
push_failures = Counter("tempo_distributor_push_failures_total",
                        "failed pushes")
live_traces = Gauge("tempo_ingester_live_traces", "live traces per tenant")
flush_failures = Counter("tempo_ingester_failed_flushes_total",
                         "block completions that failed and were backed off")
blocks_completed = Counter("tempo_ingester_blocks_completed_total",
                           "blocks completed to the backend")
query_seconds = Histogram("tempo_query_seconds", "query latency")
search_inspected = Counter("tempo_search_inspected_traces_total",
                           "traces inspected by search")
compactions = Counter("tempodb_compaction_runs_total", "compaction runs")
retention_deleted = Counter("tempodb_retention_deleted_total",
                            "blocks hard-deleted by retention")
scan_dispatches = Counter(
    "tempo_search_scan_dispatches_total",
    "scan kernel dispatches by mode (batched, coalesced, "
    "host_fallback) and by shards: the size of the mesh the launch ran "
    "over, 1 for a one-device launch or a host scan")
topk_dispatches = Counter(
    "tempo_search_topk_dispatches_total",
    "scan kernel launches by the top-k path their shape takes "
    "(engine.topk_row_width): path=rows, a tournament on row maxima, "
    "or path=direct, one sort of a small input")
mesh_param_placements = Counter(
    "tempo_search_mesh_param_placements_total",
    "mesh scan launches by where their query parameters were: "
    "result=reused, resident on the mesh from an earlier launch of the "
    "predicate, or result=placed, put there by this launch (a first "
    "launch, a predicate the batcher no longer memoises, every fused "
    "launch's stacked tables); never moves off a mesh")
launch_param_puts = Counter(
    "tempo_search_launch_param_puts_total",
    "host arrays a scan launch transferred to the device(s) for its "
    "query tables, counted in its build stage by mode=batched (a solo "
    "launch: 0 when the predicate's parameters are resident, the two "
    "tables and the bounds not memoised by value on its first), "
    "coalesced (a fused launch: its one packed buffer, and the block "
    "-> group rows where a member brings a hit mask) or mesh (either, "
    "on a mesh: arrays, not device copies)")
launch_out_fetches = Counter(
    "tempo_search_launch_out_fetches_total",
    "host arrays the drain of a scan launch fetched from the device(s), "
    "by the launch's mode=batched|coalesced|mesh: one a launch, its "
    "packed output (count, inspected, scores, idx and the ?agg= counts "
    "in one int32 array; a fused launch's is fetched once, by the first "
    "member to drain); under scan_dispatches of the same modes by the "
    "launches no drain waited for (a search that filled its limit)")
batch_cache_events = Counter("tempo_search_batch_cache_events_total",
                             "staged-batch HBM cache hits/misses/evictions")
group_picks = Counter(
    "tempo_search_group_picks_total",
    "groups a search took on the device route, by the rule that chose "
    "each from the cache as it was at that step: pick=resident (in HBM), "
    "pick=joined (none resident; it waited on a put another search had "
    "under way) or pick=staged (neither: this search, or its own "
    "look-ahead, staged it)")
coalesced_queries = Counter(
    "tempo_search_coalesced_queries_total",
    "queries served through fused multi-query scan dispatches; the "
    "coalesce ratio is this over scan_dispatches{mode=coalesced}")
coalesce_wait_seconds = Histogram(
    "tempo_search_coalesce_wait_seconds",
    "time a query spent waiting in the coalescing window before its "
    "fused dispatch launched",
    buckets=(0.0005, 0.001, 0.002, 0.005, 0.01, 0.025, 0.05, 0.1))
prepare_memo = Counter(
    "tempo_search_prepare_memo_total",
    "per-group lookups of the batcher's (batch, predicate) prepare "
    "memo by result (hit|miss); a miss pays the per-block predicate "
    "compile on the host")
frontend_queue_duration = Histogram(
    "tempo_query_frontend_queue_duration_seconds",
    "time a frontend sub-request waited in the per-tenant fair queue "
    "before a worker started it (reference: "
    "cortex_query_frontend_queue_duration_seconds)",
    buckets=(0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5))
device_timeline_dropped = Counter(
    "tempo_search_device_timeline_dropped_total",
    "kernel launches left off the traced device timeline (no "
    "`device.scan` span): the watcher's queue was full, or the span's "
    "export raised")
fallback_scans = Counter("tempo_search_fallback_scans_total",
                         "trace-block proto scans for blocks lacking "
                         "search data")
truncated_tag_entries = Counter(
    "tempo_search_truncated_entries_total",
    "entries whose tag set exceeded the kv-slot capacity at block build")

# ---- dispatch profiler (observability/profile.py) ----
dispatch_stage_seconds = Histogram(
    "tempo_search_dispatch_stage_seconds",
    "per-dispatch stage wall time: stage=build|h2d|compile|execute|d2h|"
    "lock_wait, mode=batched|coalesced|mesh|dict_probe|host_probe",
    buckets=(0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1,
             5, 30))
jit_cache_events = Counter(
    "tempo_search_jit_cache_events_total",
    "dispatch-shape compile-cache outcomes (result=hit|miss); a miss "
    "means that dispatch paid XLA trace+compile")
scan_jit_keys = Gauge(
    "tempo_search_scan_jit_keys",
    "distinct jit keys (compile_check shape signatures) the scan "
    "program's launches have shown since the process started: page "
    "bucket x block-axis bucket x (Q, T, R) x top-k; a blocklist that "
    "changes adds keys only when a group crosses a power of two")
launch_table_rows = Counter(
    "tempo_search_launch_table_rows_total",
    "rows of the per-block query tables that scan launches carried, "
    "once for each launch and member: kind=real (the group's blocks) "
    "or kind=pad (rows that fill the block count's power-of-two "
    "bucket: key id -1, no page names one)")
h2d_bytes = Counter("tempo_search_h2d_bytes_total",
                    "bytes staged host->device (pages, dictionaries, "
                    "query tables)")
d2h_bytes = Counter("tempo_search_d2h_bytes_total",
                    "bytes fetched device->host (scan results/demux)")
hbm_cache_bytes = Gauge("tempo_search_hbm_cache_bytes",
                        "staged-batch HBM cache occupancy (bytes)")
hbm_cache_peak_bytes = Gauge(
    "tempo_search_hbm_cache_peak_bytes",
    "high water of tempo_search_hbm_cache_bytes since the process "
    "started: how far the staged-batch cache ever stood over "
    "search_batch_cache_bytes (pinned in-flight groups)")
hbm_evicted_bytes = Counter(
    "tempo_search_hbm_evicted_bytes_total",
    "bytes of staged batches dropped from the HBM cache (LRU pressure "
    "and ownership rebalances); the events are "
    "batch_cache_events{result=evict}")
host_cache_bytes = Gauge("tempo_search_host_cache_bytes",
                         "host-RAM stacked-batch tier occupancy (bytes)")
probe_dict_bytes = Gauge("tempo_search_probe_dict_bytes",
                         "HBM held by staged device-probe dictionaries "
                         "across resident batches (bytes)")
probe_mask_bytes = Gauge(
    "tempo_search_probe_mask_bytes",
    "HBM pinned by device-probe hit masks (a term whose hits are more "
    "than dict_probe.R_MAX runs of its sorted dictionary), "
    "held_by=probe_cache (the [T, v_pad] products of the compile cache, "
    "at most 8 a dictionary, charged to no budget) or held_by=memo (the "
    "[G, T, Vmax] stacks of the batcher's prepare memo, charged to "
    "their staged batch under search_batch_cache_bytes)")
probe_mask_peak_bytes = Gauge(
    "tempo_search_probe_mask_peak_bytes",
    "high water of tempo_search_probe_mask_bytes, both holders summed, "
    "since the process started")
dict_probes = Counter(
    "tempo_search_dict_probes_total",
    "substring probes of one value dictionary at query compile, by "
    "path=device (the staged dictionary's kernel), path=host (numpy or "
    "the native memmem walk: dictionaries under "
    "search_device_probe_min_vals, the breaker's host route, an "
    "oversized needle) or path=cached (the compile cache had the "
    "product)")
scan_membership = Counter(
    "tempo_search_scan_membership_total",
    "members of scan launches by how the launch tests value "
    "membership: path=range (compares against [lo, hi] id ranges, no "
    "gather) or path=mask (a gather from the device probe's hit mask "
    "for every slot of every entry); one for each launch and member")
scan_range_compare = Counter(
    "tempo_search_scan_range_compare_total",
    "members of scan launches that have tag terms by what the launch's "
    "range compares test, read from its ranges a term: by=slot (under multiblock."
    "ENTRY_RANGES: every kv slot of every entry against every range, "
    "one pass) or by=entry (from there on: the entry's value for the "
    "term's key, a pass for each value an entry has for it); one for "
    "each launch and member, beside tempo_search_scan_membership_total")
hbm_logical_bytes = Gauge("tempo_search_hbm_logical_bytes",
                          "unpacked-layout equivalent of the staged-batch "
                          "HBM occupancy — equals tempo_search_hbm_cache_"
                          "bytes unless search_packed_residency narrows "
                          "the resident columns")
host_logical_bytes = Gauge("tempo_search_host_logical_bytes",
                           "unpacked-layout equivalent of the host-RAM "
                           "stacked-batch tier occupancy")
coalesce_pending = Gauge("tempo_search_coalesce_pending_queries",
                         "queries parked in coalescing windows right now "
                         "(the coalescer queue depth)")
structural_stack_events = Counter(
    "tempo_search_structural_stack_events_total",
    "structural-query stacking outcomes at coalescer flush: "
    "result=stacked (member of a fused same-plan dispatch), "
    "stacked_bucketed (member of a fused MIXED-plan dispatch whose "
    "plans canonicalized into one bucket shape — "
    "search_structural_bucket_enabled), solo_shape (no peer shared "
    "the plan shape within the window), solo_disabled "
    "(search_structural_stack_enabled off) — unstackable plan shapes "
    "are visible here instead of silently flushing solo")
structural_launches = Counter(
    "tempo_search_structural_launches_total",
    "scan launches that evaluated a structural plan over staged span "
    "columns, by how the plan joins spans: rel=none (span-scope "
    "predicates and aggregates only), child (one gather through the "
    "parent column), desc (one running max over the span axis, laid "
    "out depth first inside every trace)")
structural_span_reorder_rows = Counter(
    "tempo_search_structural_span_reorder_rows_total",
    "span rows of the blocks staging stacked, by whether laying a "
    "trace's spans out depth first (every span directly before its "
    "subtree: what the `desc` join's running max reads) had to move "
    "the row: moved=yes, or moved=no for a row stored where the layout "
    "wants it. How far a tenant's stored order is from the layout")
structural_span_order_seconds = Counter(
    "tempo_search_structural_span_order_seconds_total",
    "host seconds staging spent laying blocks' spans out depth first, "
    "the sort and the permuted copies of the span columns: paid once "
    "a block when a group's host columns are stacked")
structural_span_rows = Counter(
    "tempo_search_structural_span_rows_total",
    "span rows put on the device with staged groups: kind=live (spans "
    "of the group's traces) or kind=pad (rows that fill a block's last "
    "tile of the span axis, and the axis to its power of two: every "
    "pass of a launch reads them too)")
structural_span_bytes = Gauge(
    "tempo_search_structural_span_bytes",
    "HBM held by the span columns of the groups resident in the "
    "staged-batch cache now (pad rows included); part of "
    "tempo_search_hbm_cache_bytes")

# ---- hot-tier live search (search/live_tier.py) ----
live_tier_entries = Gauge(
    "tempo_search_live_tier_entries",
    "in-flight traces held in the hot tier's per-tenant live stage "
    "(absorbed at push, evicted at cut)")
live_tier_scans = Counter(
    "tempo_search_live_tier_scans_total",
    "hot-tier live-stage scan outcomes (result=scan: answered by the "
    "fused kernel; fallback_overflow: stage past "
    "search_live_tier_max_entries, legacy walk ran; fallback: scan "
    "declined, legacy walk ran)")
live_tier_rebuilds = Counter(
    "tempo_search_live_tier_rebuilds_total",
    "columnar stage rebuilds (one per absorbed/evicted epoch actually "
    "searched — consecutive mutations between searches coalesce into "
    "one rebuild)")
live_tier_evictions = Counter(
    "tempo_search_live_tier_evictions_total",
    "entries leaving the live stage (reason=cut: trace cut to the WAL "
    "head, where the hot scan still covers it)")
live_tail_subscriptions = Gauge(
    "tempo_search_live_tail_subscriptions",
    "standing tail subscriptions registered per tenant")
live_tail_notifications = Counter(
    "tempo_search_live_tail_notifications_total",
    "tail notifications delivered to standing-query subscribers")
live_tail_dropped = Counter(
    "tempo_search_live_tail_dropped_total",
    "tail notifications/registrations dropped per tenant (reason=queue: "
    "a slow consumer's bounded queue overflowed, oldest dropped; cap: "
    "subscribe rejected at search_live_tail_max_subscriptions)")

# ---- SSE streaming surfaces (api/http.py /api/search/stream, /api/tail)
sse_active_streams = Gauge(
    "tempo_sse_active_streams",
    "SSE responses currently being written per tenant "
    "(endpoint=search_stream|tail) — live-tail SUBSCRIPTIONS are "
    "tempo_search_live_tail_subscriptions; this counts the HTTP legs, "
    "including ones draining after their subscription lapsed")
sse_events_streamed = Counter(
    "tempo_sse_events_total",
    "SSE events written to clients per tenant "
    "(endpoint=search_stream|tail, event = the SSE event name: "
    "result|trace|summary|subscribed|end|error|keepalive)")

# ---- device-side aggregate analytics (search/analytics.py) ----
search_analytics_dispatches = Counter(
    "tempo_search_analytics_dispatches_total",
    "aggregate-analytics count dispatches (route=device: the dense "
    "count kernel ran on the accelerator; host: breaker-open or "
    "overflow fallback computed the byte-identical numpy counts)")
search_analytics_staged_bytes = Gauge(
    "tempo_search_analytics_staged_bytes",
    "bytes staged to the device for the most recent analytics "
    "micro-batch (pow2-tier padded row columns)")
# the query side: ?agg= reductions fused onto the scan launches
agg_launches = Counter(
    "tempo_search_agg_launches_total",
    "scan launches that carried the ?agg= reduction, by the dispatch "
    "modes of tempo_search_scan_dispatches_total (a fused launch is one "
    "however many members reduce in it)")
agg_key_rows = Counter(
    "tempo_search_agg_key_rows_total",
    "key rows the ?agg= reductions took in: members x the staged "
    "group's entries, pad pages included (what the launches sorted)")
agg_staged_bytes = Gauge(
    "tempo_search_agg_staged_bytes",
    "HBM held by the ?agg= key columns of the resident groups; part of "
    "tempo_search_hbm_cache_bytes")
agg_stage_seconds = Histogram(
    "tempo_search_agg_stage_seconds",
    "one staged group's ?agg= key column: its build on the host and "
    "its put on the device, fenced",
    buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0))
# ---- owner-routed HBM (search/ownership.py) ----
hbm_owner_generation = Gauge(
    "tempo_search_hbm_owner_generation",
    "ownership-map membership generation this process placed against; "
    "fleet members disagreeing here are mid-rebalance")
hbm_owner_groups = Gauge(
    "tempo_search_hbm_owner_groups",
    "placement groups this member owns under the current generation")
hbm_owner_rebalance_moves = Counter(
    "tempo_search_hbm_owner_rebalance_moves_total",
    "placement groups whose owner changed at a membership generation "
    "bump — the rebalance is a placement diff, never a cache flush")
hbm_owner_routed = Counter(
    "tempo_search_hbm_owner_routed_total",
    "batcher group routing decisions while ownership is enabled "
    "(route=owner|non_owner_host: device-resident serve vs the "
    "byte-identical host route on a non-owner)")
hbm_owner_rebalance_evictions = Counter(
    "tempo_search_hbm_owner_rebalance_evictions_total",
    "HBM batches released because a rebalance moved their group away "
    "(result=dropped|deferred; deferred batches drop at unpin)")
hbm_replica_promotions = Counter(
    "tempo_search_hbm_replica_promotions_total",
    "heat-table replica-set transitions (dir=up: a placement group's "
    "access rate crossed search_hbm_ownership_hot_rate and promoted to "
    "its rf-deep replica set; dir=down: rate decayed below the "
    "hysteresis floor and the group demoted back to its single owner)")
hedged_dispatches = Counter(
    "tempo_search_hedged_dispatches_total",
    "frontend hedged-dispatch outcomes over promoted groups "
    "(result=primary: primary answered inside the hedge delay; "
    "hedge_won: the replica's duplicate answered first; cancelled: a "
    "losing in-flight attempt was expired through its deadline)")

# ---- offload planner (search/planner.py) ----
offload_decisions = Counter(
    "tempo_search_offload_decisions_total",
    "offload-planner probe placements (target=host|device, "
    "site=stage|compile|offline); only counted while the planner is "
    "enabled — the static-threshold path books nothing")
offload_predict_error = Histogram(
    "tempo_search_offload_predict_error_ratio",
    "relative |predicted - actual| / actual of the planner's chosen-side "
    "probe cost, resolved when the matching probe run is observed",
    buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.0, 5.0))

# ---- per-query execution inspector (search/query_stats.py) ----
query_device_seconds = Counter(
    "tempo_search_query_device_seconds_total",
    "device-seconds attributed to queries per tenant: fused coalesced "
    "dispatches apportion their stage times across member queries by "
    "padded predicate rows (shares sum to the dispatch total), so this "
    "is the fleet's device-time bill by tenant")
query_bytes_inspected = Counter(
    "tempo_search_query_bytes_inspected_total",
    "bytes inspected by queries per tenant, split by placement=device "
    "(scan kernels over staged batches) vs placement=host (fallback "
    "proto scans, host dictionary probes)")
query_stage_seconds = Histogram(
    "tempo_search_query_stage_seconds",
    "per-QUERY stage wall time: host stages (header_prune|staging|"
    "prepare|dispatch|drain|fallback_scan) plus attributed device "
    "stages (device_build|device_h2d|device_compile|device_execute|"
    "device_d2h|device_lock_wait); exemplars link buckets to "
    "self-traces",
    buckets=(0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1,
             5, 30))
slow_queries = Counter(
    "tempo_search_slow_queries_total",
    "queries slower than search_slow_query_log_s per tenant, booked "
    "ONCE per query per process (in-process sub-requests of a slow "
    "request don't re-count); the log line is additionally rate-limited "
    "per tenant")

# ---- write-path telemetry (observability/ingest_telemetry.py) ----
ingest_stage_seconds = Histogram(
    "tempo_ingest_stage_seconds",
    "write-path stage latency: stage=push_ack (distributor accept+"
    "replicate wall time) | live_cut (trace first-push -> cut into the "
    "WAL head) | block_cut (head-block age when cut for completion) | "
    "flush (block cut -> backend flush success, queue wait included) | "
    "flush_write (the backend completion write itself) | poll_visible "
    "(flush success -> first poll that lists the block) | "
    "push_to_searchable (oldest trace push -> poll visibility, the "
    "end-to-end freshness a reader actually experiences)",
    buckets=(0.001, 0.005, 0.025, 0.1, 0.5, 1, 5, 15, 60, 300, 1800))
search_freshness = Gauge(
    "tempo_search_freshness_seconds",
    "per-tenant search staleness: now - max end_time over the tenant's "
    "newest SEARCHABLE (polled) block; refreshed every poll cycle")
oldest_unflushed = Gauge(
    "tempo_ingest_oldest_unflushed_seconds",
    "per-tenant age of the oldest trace not yet flushed to the backend "
    "— live (uncut), WAL head, or completing blocks; 0 when everything "
    "is flushed")
flush_duration_seconds = Histogram(
    "tempo_ingester_flush_duration_seconds",
    "successful block completion (WAL -> backend) wall time per flush",
    buckets=(0.01, 0.05, 0.25, 1, 5, 30, 120, 600))
flush_queue_length = Gauge(
    "tempo_ingester_flush_queue_length",
    "per-tenant blocks cut and waiting for (or in) backend completion")
flush_retries = Counter(
    "tempo_ingester_flush_retries_total",
    "flush attempts that failed and were backed off, labeled by "
    "attempt bucket (attempt=1|2|3|4+) — distinguishes a one-off "
    "backend flake from a block stuck in exponential backoff")
wal_replay_seconds = Gauge(
    "tempo_ingester_wal_replay_seconds",
    "duration of the WAL replay this process performed at startup")
wal_replayed_blocks = Gauge(
    "tempo_ingester_wal_replayed_blocks",
    "WAL blocks replayed at startup")
wal_replayed_bytes = Gauge(
    "tempo_ingester_wal_replayed_bytes",
    "WAL bytes re-scanned at startup")
slow_flushes = Counter(
    "tempo_ingester_slow_flushes_total",
    "flushes slower than ingest_slow_flush_log_s per tenant (every one "
    "counts; the JSON log line is additionally rate-limited per tenant)")
blocklist_poll_seconds = Histogram(
    "tempodb_blocklist_poll_duration_seconds",
    "blocklist poll cycle wall time (backend list + meta reads + apply)",
    buckets=(0.005, 0.025, 0.1, 0.5, 2, 10, 60, 300))
blocklist_length = Gauge(
    "tempodb_blocklist_length",
    "per-tenant live blocks in this reader's blocklist after the last "
    "poll")
blocklist_index_age = Gauge(
    "tempodb_blocklist_index_age_seconds",
    "per-tenant age of the tenant index this poller last consumed "
    "(now - builder created_at); a growing value means the elected "
    "index builder stopped writing")
compaction_duration_seconds = Histogram(
    "tempodb_compaction_duration_seconds",
    "one compaction run (k-way merge + search rebuild) wall time",
    buckets=(0.05, 0.25, 1, 5, 30, 120, 600))
compaction_outstanding_bytes = Gauge(
    "tempodb_compaction_outstanding_bytes",
    "per-tenant bytes sitting in compactable input groups (>= "
    "min_inputs same-window blocks) — the compactor's input backlog")
compaction_outstanding_blocks = Gauge(
    "tempodb_compaction_outstanding_blocks",
    "per-tenant block count behind "
    "tempodb_compaction_outstanding_bytes — backlog in selector units "
    "(one run consumes at most compaction_max_inputs of these)")
canary_freshness = Gauge(
    "tempo_ingest_canary_freshness_seconds",
    "last MEASURED push->searchable latency of the synthetic ingest "
    "canary (black-box: a real push polled through real search)")
canary_failures = Counter(
    "tempo_ingest_canary_failures_total",
    "canary probes that never became searchable before their deadline "
    "— the wedged-flush/poll alarm")

# ---- robustness: breaker / watchdog / fault injection ----
device_faults = Counter(
    "tempo_search_device_faults_total",
    "device dispatch faults booked into the circuit breaker "
    "(kind=timeout|error|lock_timeout, mode = the profiler dispatch "
    "mode giving the fault its stage context); counted even with the "
    "breaker disabled")
breaker_transitions = Counter(
    "tempo_search_device_breaker_transitions_total",
    "circuit-breaker state transitions (from/to = "
    "closed|open|half_open); open means every scan/probe is routed "
    "through the byte-identical host path")
breaker_state = Gauge(
    "tempo_search_device_breaker_state",
    "current breaker state as a code: 0=closed 1=half_open 2=open")
dispatch_lock_timeouts = Counter(
    "tempo_search_dispatch_lock_timeouts_total",
    "bounded waits on the process-wide collective dispatch lock that "
    "timed out — some dispatch is wedged while holding it (each books "
    "a breaker fault kind=lock_timeout)")
partial_results = Counter(
    "tempo_search_partial_results_total",
    "sub-answers swallowed into a DEGRADED response, by why "
    "(reason=replica|backend|subrequest|deadline), booked at the "
    "swallow site — a failure past tolerate_failed_blocks still "
    "counts here even though the request then errors. The "
    "response-level twin is SearchMetrics.partial, which survives the "
    "frontend merge so a degraded answer is never indistinguishable "
    "from a complete one")
faults_injected = Counter(
    "tempo_robustness_faults_injected_total",
    "fault-injection firings per faultpoint (chaos/test harness only; "
    "always zero in production unless a faultpoint is armed)")

# ---- self-tracing health (observability/tracing.py) ----
selftrace_dropped_spans = Counter(
    "tempo_selftrace_dropped_spans_total",
    "self-trace spans dropped because the batch processor queue was "
    "full, labeled by exporter class like selftrace_export_failures — "
    "and the SINGLE source of truth: BatchProcessor.dropped derives "
    "from this series")
selftrace_export_failures = Counter(
    "tempo_selftrace_export_failures_total",
    "self-trace export batches that raised (swallowed to protect the "
    "flush loop; this counter is the only visible signal)")

# ---- build identity ----
# the process's CPU clocks under upstream's names (its Prometheus
# client's process collector): read when /metrics is rendered, never on
# a request path. rate(process_cpu_seconds_total[1m]) near 1.0 on a busy
# querier says one interpreter lock is the limit, not the chip
process_cpu_seconds = Counter(
    "process_cpu_seconds_total",
    "total user and system CPU time spent by the process in seconds "
    "(every thread: the interpreter's, jax's, the profiler's)",
    read=time.process_time)
process_cpu_user_seconds = Counter(
    "process_cpu_user_seconds_total",
    "user CPU time spent by the process in seconds",
    read=lambda: os.times().user)
process_cpu_system_seconds = Counter(
    "process_cpu_system_seconds_total",
    "system CPU time spent by the process in seconds (the kernel's "
    "work for it: page faults of a large put, socket IO)",
    read=lambda: os.times().system)

build_info = Gauge(
    "tempo_build_info",
    "constant 1; the process's build/runtime identity rides the labels "
    "(version = tempo_tpu package version, jax = jax version or "
    "'absent', backend = initialized jax backend or "
    "uninitialized/unknown at set time, native = native libtempotpu.so "
    "state: loaded|present|absent|unknown) — the standard *_build_info "
    "idiom, set once at App init and mirrored live in /status")
