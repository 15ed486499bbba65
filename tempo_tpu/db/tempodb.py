"""TempoDB facade: the storage engine's public Reader/Writer/Compactor.

Role-equivalent to the reference's tempodb/tempodb.go:70-520: block
completion from WAL blocks, trace-by-ID fan-out over the blocklist with a
bounded pool, search across backend search blocks (device engine, staged
cache), poller/compaction/retention enablement, and block inclusion
predicates (id-range shard + time window).
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field

from tempo_tpu import tempopb
from tempo_tpu.backend.raw import RawBackend
from tempo_tpu.backend.types import BlockMeta
from tempo_tpu.encoding.v2 import BackendBlock, StreamingBlock
from tempo_tpu.model.codec import codec_for
from tempo_tpu.search import SearchResults, write_search_block
from tempo_tpu.search.backend_search_block import BackendSearchBlock
from tempo_tpu.search.batcher import BlockBatcher, ScanJob
from tempo_tpu.search.columnar import PageGeometry
from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import tracing
from tempo_tpu.observability.log import get_logger
from tempo_tpu.utils.ids import pad_trace_id
from tempo_tpu.utils.lru import BoundedCache
from tempo_tpu.wal import WAL, AppendBlock

from .blocklist import Blocklist
from .compaction import TimeWindowBlockSelector, compact_blocks
from .poller import Poller
from .pool import run_jobs
from .retention import apply_retention


log = get_logger("tempo_tpu.tempodb")


@dataclass
class TempoDBConfig:
    block_encoding: str = "zstd"          # reference: block zstd
    # WAL record compression (reference: snappy v2 pages, wal.go:54-97).
    # "auto" = native snappy if built, zlib otherwise; "none" disables
    wal_encoding: str = "auto"
    search_encoding: str = "zstd"         # reference: search snappy
    block_page_size: int = 1 << 20
    pool_workers: int = 50                # reference: pool 50 workers
    blocklist_poll_s: int = 30
    compaction_window_s: int = 3600
    compaction_max_inputs: int = 8
    compaction_flush_bytes: int = 30 << 20   # reference FlushSizeBytes
    complete_flush_bytes: int = 30 << 20     # completion streams at the same cadence
    retention_s: int = 14 * 24 * 3600
    compacted_retention_s: int = 3600
    search_geometry: PageGeometry = field(default_factory=PageGeometry)
    tenant_index_builder: bool = True
    search_cache_blocks: int = 64         # open search-block objects kept
    # serving-path batching (the TPU inversion of the reference's per-job
    # fan-out, searchsharding.go): blocks group into one kernel dispatch
    # pages per DEVICE and dispatch: a mesh of s devices splits a group's
    # page axis s ways, so the batcher groups s times as many pages
    search_max_batch_pages: int = 4096
    search_batch_cache_bytes: int = 4 << 30   # staged-batch HBM budget
    # host-RAM overflow tier for stacked batches: HBM-evicted batches
    # re-stage with one H2D copy instead of IO+decompress+restack.
    # None = auto: min(32 GB, half of physical RAM) — this tier RETAINS
    # memory, so a fixed default would OOM small hosts
    search_host_cache_bytes: int | None = None
    search_pipeline_depth: int = 2        # dispatches in flight
    # cross-request query coalescing: concurrent searches whose dispatch
    # hits the same staged batch within this window fuse into ONE
    # multi-query kernel launch. A solo search skips the window (no peer
    # to wait for), so serial latency is unchanged. max_queries <= 1
    # disables coalescing entirely
    search_coalesce_window_s: float = 0.003
    search_coalesce_max_queries: int = 8
    # device-resident dictionary probe: value dictionaries at/above this
    # many distinct values stage their packed bytes to HBM and run the
    # substring prefilter ON DEVICE (search/dict_probe.py) instead of
    # the host memmem walk — at 10M distinct values the host walk is
    # ~312 ms per fresh tag-set (a CPU-container host timing; the chip
    # side is not measured on today's code).
    # Mirrors pipeline.NATIVE_SCAN_THRESHOLD (the same scale at which
    # the HOST scan moves to the native memmem path); <= 0 keeps every
    # probe on the exact host path. None = the dict_probe default (50k).
    search_device_probe_min_vals: int | None = None
    # adaptive host/device offload planner (search/planner.py): above
    # the search_device_probe_min_vals floor, a cost model over the live
    # dispatch-profiler observations chooses host vs device for the
    # dictionary substring prefilter per block group at plan time —
    # self-calibrating (EWMA over recent dispatches, seeded by a
    # one-shot microbenchmark on first decision). False (default) is
    # behavior-identical to the static-threshold path. Decisions +
    # predicted-vs-actual error at /debug/planner. Both placements are
    # exact, so results never depend on this flag.
    search_offload_planner_enabled: bool = False
    # EWMA smoothing for the planner's observed rates (higher = adapt
    # faster, noisier) and the decision ring rendered by /debug/planner
    search_offload_planner_ewma: float = 0.25
    search_offload_planner_ring: int = 256
    # owner-routed HBM (search/ownership.py,
    # docs/search-hbm-ownership.md): block placement groups get
    # consistent-hash ownership across the fleet — the frontend routes a
    # group's sub-queries to its owner (the one process holding it
    # device-resident, where cross-request coalescing fuses tenants'
    # dashboards), a non-owner serves the byte-identical host route
    # instead of staging a duplicate HBM copy, and a membership change
    # moves only the affected groups (eviction becomes a placement
    # change). False (default) is a true noop: one attribute read per
    # site, byte-identical routing.
    search_hbm_ownership_enabled: bool = False
    # comma-separated fleet member ids ("host-0,host-1"); empty = auto
    # from the multihost env contract (TEMPO_NUM_PROCESSES /
    # TEMPO_PROCESS_ID), a single-member "self" fleet otherwise
    search_hbm_ownership_members: str = ""
    # this process's member id; empty = auto (matches the member
    # auto-derivation above)
    search_hbm_ownership_self: str = ""
    # placement-group count block ids hash onto (the ownership and
    # rebalance unit): more groups = finer rebalance granularity at a
    # larger /debug/ownership map
    search_hbm_ownership_groups: int = 64
    # heat-adaptive replication factor: > 1 promotes a placement group
    # whose access rate crosses the hot-rate threshold to the first rf
    # distinct members the ownership ring yields for its token —
    # replicas serve it device-resident and the frontend hedges their
    # dispatches. 1 (default) keeps single-owner placement bit for bit:
    # the heat table, replica lookups and the hedge timer are each one
    # attribute read.
    search_hbm_ownership_rf: int = 1
    # per-group access rate (scans/second, EWMA over a 30 s window)
    # that promotes a group to its replica set; demotion is hysteretic
    # at half this rate. Only meaningful with rf > 1.
    search_hbm_ownership_hot_rate: float = 50.0
    # hedge delay for replicated dispatch, in milliseconds: how long
    # the frontend waits on a promoted group's primary before firing
    # the same batch at the next replica. 0 (default) auto-derives a
    # p99-ish bound from observed dispatch walls (mean + 3*dev, seeded
    # by the dispatch profiler's stage EWMAs).
    search_hedge_delay_ms: float = 0.0
    # structural query engine (search/ir.py + search/structural.py,
    # docs/search-structural-queries.md): a typed query IR — span-level
    # predicates, AND/OR/NOT, parent-child / descendant relations,
    # count and duration-quantile aggregates — parsed from ?q= on the
    # search API and COMPILED into the fused scan kernels (parent-
    # pointer joins + segment reductions over per-trace span segments).
    # Enabling also captures per-span summary rows at ingest (the span
    # segment of new search containers). False (default) is a true
    # noop: legacy tag/duration requests read one attribute and take
    # the existing byte-identical path; requests carrying ?q= get a 400.
    search_structural_enabled: bool = False
    # span rows captured per trace at ingest (walk-order truncation —
    # the span segment's max_search_bytes analog)
    search_structural_max_spans: int = 512
    # kv pairs captured per span at ingest
    search_structural_max_span_kvs: int = 16
    # plan-shape query stacking: concurrent structural queries that
    # lowered to the SAME static plan descriptor stack along the
    # coalescer's query axis (parameter tables pad to the group max)
    # and execute as ONE fused dispatch — N dashboards running the
    # same saved query cost ~1 kernel launch per coalescing window.
    # Unstackable shapes flush solo and surface in
    # tempo_search_structural_stack_events_total. False (default) is a
    # true noop: structural queries keep the solo-flush behavior
    # exactly (one attribute read at the coalescer).
    search_structural_stack_enabled: bool = False
    # segment-aligned span sharding on mesh/dist staging: the span
    # segment reshards so each trace's contiguous span run lands whole
    # on its page's shard (parent pointers and segment ranges rebased
    # shard-local), making the child gather and desc pointer-doubling
    # shard-local — parent joins scale with the mesh and per-shard span
    # HBM drops to ~1/P of the replicated layout. False (default) is a
    # true noop: span columns replicate exactly as before (one
    # attribute read at the placement sites).
    search_structural_shard_spans: bool = False
    # shape-bucketed cross-plan stacking: concurrent structural queries
    # whose DIFFERENT plans canonicalize into the same bucket shape
    # (node count rounded to a pow2 tier, relation/aggregate slots
    # masked per member) stack into ONE coalesced dispatch — mixed
    # dashboard traffic fuses instead of flushing one short dispatch
    # per plan. Inactive slots evaluate as identity, so results stay
    # byte-identical to solo execution. False (default) is a true noop:
    # stack_group_key keeps exact-plan grouping (one attribute read).
    search_structural_bucket_enabled: bool = False
    # largest flattened slot count (span + trace nodes) a plan may
    # occupy and still bucket; bigger plans keep exact-plan grouping
    search_structural_bucket_max_nodes: int = 16
    # remainder-shard mesh layout: stage to the smallest multiple of
    # n_shards instead of the next pow2, with the ragged tail recorded
    # as a static per-shard valid length in the jit key — a 9-page
    # block on 8 shards stages 16 pages today, 2x the bytes it needs.
    # False (default) is a true noop: pow2 staging exactly as before
    # (one attribute read at the staging site).
    search_structural_remainder_pages: bool = False
    # hot-tier live search (search/live_tier.py,
    # docs/search-live-tail.md): the ingesters' in-flight traces absorb
    # into a per-tenant rolling columnar stage scanned by the SAME
    # fused kernel as backend blocks (pow2-capacity tiers keep the jit
    # key shape-only), the WAL head/completing generations kernel-scan
    # through the identical machinery, and standing tail subscriptions
    # evaluate per push micro-batch — push→searchable drops from
    # flush+poll (seconds) to one absorb+scan (sub-100ms on chip).
    # False (default) is a true noop: every hook reads one attribute;
    # live/WAL search keeps the per-entry host walk byte-identically.
    search_live_tier_enabled: bool = False
    # live-stage entry ceiling per tenant: past it a search falls back
    # to the legacy walk (counted in
    # tempo_search_live_tier_scans_total{result=fallback_overflow})
    search_live_tier_max_entries: int = 4096
    # standing tail subscriptions allowed per tenant; registration past
    # the cap is rejected (429 on /api/tail)
    search_live_tail_max_subscriptions: int = 16
    # packed HBM residency (search/packing.py,
    # docs/search-packed-residency.md): staged value-id columns narrow
    # to the width the per-block dictionary cardinality allows (4-bit/
    # uint8/uint16/uint32 codes), durations quantize to uint16 buckets
    # with an exact residual check at bucket boundaries, and device-
    # probe hit masks bit-pack to uint32 words — kernels unpack
    # in-register (the width descriptor is part of the jit shape key),
    # so ~2x more blocks fit a given HBM budget at byte-identical
    # results. False (default) is a true noop: one attribute read per
    # staging site, byte-identical layout and results.
    search_packed_residency: bool = False
    # device-side aggregate analytics (search/analytics.py,
    # docs/search-analytics.md): the metrics generator's native
    # summary-row feed batches into rolling pow2-tier device
    # micro-batches — calls/errors by (service, span_name, kind,
    # status), exact latency-bucket counts, and service-graph edge
    # counts compute as ONE dense sorted-key reduction per push, and
    # the host drains per-series deltas into the same ManagedRegistry
    # handles (byte-identical to the per-span walk); at query time
    # ?agg=red compiles group-by-service RED answers onto the fused
    # scan dispatch. False (default) is a true noop: one attribute
    # read per push / per search, walk and response byte-identical.
    search_analytics_enabled: bool = False
    # blobs under this many rows stay on the per-span walk (batch
    # setup costs more than it saves on tiny pushes)
    search_analytics_min_rows: int = 64
    # stage + compile-warm hot batches in the background after each poll
    # so the first query pays neither (off by default: polls in tests and
    # write-only processes must not spin up device work)
    search_prewarm_on_poll: bool = False
    # dispatch profiler (observability/profile.py): per-dispatch stage
    # breakdown (build/h2d/compile/execute/d2h/lock_wait) into
    # tempo_search_dispatch_stage_seconds + /debug/profile. False is a
    # TRUE noop — dispatch sites get a shared noop record, no clock
    # reads, no locks (tests/test_observability.py
    # test_profiler_noop_path_is_shared_and_cheap; what profiling costs
    # when ON is not measured on the chip)
    search_profiling_enabled: bool = True
    # block_until_ready fence after each profiled kernel call: attributes
    # TRUE kernel time to the execute stage, at the cost of the async
    # dispatch/drain pipelining — triage sessions only
    search_profiling_fence: bool = False
    # recent-dispatch ring rendered by /debug/profile
    search_profiling_ring: int = 256
    # per-query execution inspector (search/query_stats.py): every
    # search accumulates blocks scanned/skipped (and why), bytes split
    # host vs device, cache hits vs re-stages, planner decisions, and
    # per-stage device-seconds attributed from its (possibly fused)
    # dispatches — feeding the per-tenant accounting counters, the
    # slow-query log, /debug/querystats, and the opt-in ?explain=1
    # response breakdown. False is a true noop on the search path
    # (bench phase query_stats_overhead asserts the contract);
    # results are byte-identical either way.
    search_query_stats_enabled: bool = True
    # slow-query log threshold (seconds): a query slower than this
    # emits ONE structured JSON log line (tenant, self-trace id, the
    # complete QueryStats), rate-limited process-wide. <= 0 disables
    # the log; the tempo_search_slow_queries_total counter still counts.
    search_slow_query_log_s: float = 10.0
    # recent-query ring rendered by /debug/querystats
    search_query_stats_ring: int = 256
    # ---- robustness (tempo_tpu/robustness/, docs/robustness.md) ----
    # watchdog deadline per DEVICE dispatch (single/batched/coalesced/
    # mesh/dict-probe kernels, staging H2D puts, drain D2H syncs): a
    # dispatch that exceeds it is abandoned, booked as a device fault,
    # and answered through the byte-identical host path. <= 0 disables
    # the watchdog (faults are still classified). Only consulted while
    # the breaker is enabled or a faultpoint is armed — breaker off +
    # faults disarmed is a true noop on the dispatch path.
    search_device_dispatch_timeout_s: float = 30.0
    # bounded wait on the process-wide collective dispatch lock
    # (parallel.mesh.dispatch_lock): a timeout books a breaker fault
    # instead of blocking the submitter forever (the PR 1
    # rendezvous-deadlock class, detectable at runtime). <= 0 = wait
    # forever (the historical behavior)
    search_dispatch_lock_timeout_s: float = 60.0
    # default request deadline for /api/search and /api/traces when the
    # client sends no X-Tempo-Timeout-S header; propagates http →
    # frontend → querier → TempoDB so sharded sub-queries stop queueing
    # once the budget is spent (the answer goes out PARTIAL). 0 = no
    # default deadline
    search_request_timeout_s: float = 0.0
    # device circuit breaker: search_breaker_fault_threshold faults
    # within search_breaker_window_s trip it open; while open every
    # scan/probe runs the byte-identical host path; after
    # search_breaker_cooldown_s it half-opens and probes the device
    # with real dispatches until one succeeds (closed) or fails (open
    # again). False disables the whole robustness layer (the noop
    # contract: tests/test_faults.py test_breaker_disabled_is_passthrough,
    # test_disarmed_noop_byte_identity).
    search_breaker_enabled: bool = True
    search_breaker_fault_threshold: int = 3
    search_breaker_window_s: float = 30.0
    search_breaker_cooldown_s: float = 5.0
    # fault-injection arming spec ("name:p=1,count=2,delay=0.5;..." —
    # see tempo_tpu/robustness/faults.py); the TEMPO_FAULTS env var arms
    # in addition. Empty (default) = nothing armed, true noop.
    robustness_faults: str = ""
    # shard batches over the device mesh when >1 device is visible
    auto_mesh: bool = True
    # restartable host state: persistent XLA compile cache (placed by
    # utils.jaxenv.enable_compile_cache, never under this directory) +
    # header snapshot. None = auto (snapshot under <wal_dir>/host-state);
    # "" disables both; a path overrides the snapshot location. A cold
    # restart then replays compiles from disk and loads header rollups
    # without one backend read per block.
    host_state_dir: str | None = None


class TempoDB:
    """Reader + Writer + Compactor over one backend."""

    def __init__(self, backend: RawBackend, wal_dir: str,
                 cfg: TempoDBConfig | None = None, mesh=None):
        """mesh: a jax.sharding.Mesh to shard batched scans over; when
        None and cfg.auto_mesh is set, a 1-axis mesh over all visible
        devices is built automatically if more than one is present."""
        self.backend = backend
        self.cfg = cfg or TempoDBConfig()
        # degrade unusable codecs up front: a host without the native
        # build AND without the zstandard wheel cannot zstd — writing
        # must fall back to an always-available codec (data is labeled
        # with the codec that actually wrote it; READS of existing zstd
        # blocks still fail loudly, which is correct)
        from tempo_tpu.encoding.v2.compression import best_available

        import dataclasses

        for _field in dataclasses.fields(self.cfg):
            if _field.name not in ("block_encoding", "search_encoding"):
                continue
            _enc = getattr(self.cfg, _field.name)
            if _enc != _field.default:
                # an explicit non-default codec choice fails fast on
                # first use — silently rewriting it would mask a broken
                # deployment (missing native lib the operator asked for)
                continue
            _use = best_available(_enc)
            if _use != _enc:
                log.warning("%s %r unusable on this host (no native lib/"
                            "wheel); degrading to %r", _field.name, _enc,
                            _use)
                # degrade a COPY: the caller's config object is theirs —
                # writing into it would leak this host's fallback into
                # other TempoDBs built from the same config
                self.cfg = dataclasses.replace(
                    self.cfg, **{_field.name: _use})
        self.wal = WAL(wal_dir, encoding=self.cfg.wal_encoding)
        self.blocklist = Blocklist()
        self.poller = Poller(backend, build_index=self.cfg.tenant_index_builder)
        self.selector = TimeWindowBlockSelector(
            window_s=self.cfg.compaction_window_s,
            max_inputs=self.cfg.compaction_max_inputs,
        )
        self.mesh = mesh
        # auto-mesh resolves lazily on the first search: jax.devices()
        # initializes the backend (and on TPU hosts claims the chip), which
        # write/compact-only processes must never pay for
        self._mesh_resolved = mesh is not None
        self.batcher = BlockBatcher(
            mesh=mesh,
            max_batch_pages=self.cfg.search_max_batch_pages,
            cache_bytes=self.cfg.search_batch_cache_bytes,
            host_cache_bytes=self.cfg.search_host_cache_bytes,
            pipeline_depth=self.cfg.search_pipeline_depth,
            coalesce_window_s=self.cfg.search_coalesce_window_s,
            coalesce_max_queries=self.cfg.search_coalesce_max_queries,
            device_probe_min_vals=self.cfg.search_device_probe_min_vals,
        )
        # the profiler is process-wide (like REGISTRY): the most recent
        # TempoDB's config wins, matching how metrics/tracing configure
        from tempo_tpu.observability import profile as _profile

        _profile.configure(enabled=self.cfg.search_profiling_enabled,
                           fence=self.cfg.search_profiling_fence,
                           ring_size=self.cfg.search_profiling_ring)
        # per-query stats: process-wide like the profiler (most recent
        # TempoDB's config wins, the REGISTRY idiom)
        from tempo_tpu.search import query_stats as _query_stats

        _query_stats.configure(
            enabled=self.cfg.search_query_stats_enabled,
            slow_s=self.cfg.search_slow_query_log_s,
            ring_size=self.cfg.search_query_stats_ring)
        # robustness layer: breaker + dispatch watchdog + fault
        # registry, process-wide like the profiler (most recent
        # TempoDB's config wins, the REGISTRY idiom)
        from tempo_tpu import robustness as _robustness

        _robustness.configure(
            breaker_enabled=self.cfg.search_breaker_enabled,
            fault_threshold=self.cfg.search_breaker_fault_threshold,
            window_s=self.cfg.search_breaker_window_s,
            cooldown_s=self.cfg.search_breaker_cooldown_s,
            dispatch_timeout_s=self.cfg.search_device_dispatch_timeout_s,
            lock_timeout_s=self.cfg.search_dispatch_lock_timeout_s,
            faults_spec=self.cfg.robustness_faults)
        # offload planner: process-wide like the profiler it feeds from
        from tempo_tpu.search import planner as _planner

        _planner.configure(enabled=self.cfg.search_offload_planner_enabled,
                           alpha=self.cfg.search_offload_planner_ewma,
                           ring_size=self.cfg.search_offload_planner_ring)
        # packed HBM residency: process-wide gate like the layers above
        # (docs/search-packed-residency.md)
        from tempo_tpu.search import packing as _packing

        _packing.configure(enabled=self.cfg.search_packed_residency)
        # structural query engine: process-wide gate like the layers
        # above (docs/search-structural-queries.md)
        from tempo_tpu.search import structural as _structural

        _structural.configure(
            enabled=self.cfg.search_structural_enabled,
            max_spans=self.cfg.search_structural_max_spans,
            max_span_kvs=self.cfg.search_structural_max_span_kvs,
            stack_enabled=self.cfg.search_structural_stack_enabled,
            shard_spans=self.cfg.search_structural_shard_spans,
            bucket_enabled=self.cfg.search_structural_bucket_enabled,
            bucket_max_nodes=self.cfg.search_structural_bucket_max_nodes,
            remainder_pages=self.cfg.search_structural_remainder_pages)
        # hot-tier live search: process-wide gate like the layers above
        # (docs/search-live-tail.md)
        from tempo_tpu.search.live_tier import LIVE_TIER as _live_tier

        _live_tier.configure(
            enabled=self.cfg.search_live_tier_enabled,
            max_entries=self.cfg.search_live_tier_max_entries,
            max_subscriptions=self.cfg.search_live_tail_max_subscriptions)
        # device-side aggregate analytics: process-wide gate like the
        # layers above (docs/search-analytics.md)
        from tempo_tpu.search.analytics import ANALYTICS as _analytics

        _analytics.configure(
            enabled=self.cfg.search_analytics_enabled,
            min_rows=self.cfg.search_analytics_min_rows)
        # owner-routed HBM placement: process-wide like the layers above
        # (docs/search-hbm-ownership.md)
        from tempo_tpu.search import ownership as _ownership

        _ownership.configure(
            enabled=self.cfg.search_hbm_ownership_enabled,
            members=self.cfg.search_hbm_ownership_members or None,
            self_id=self.cfg.search_hbm_ownership_self or None,
            groups=self.cfg.search_hbm_ownership_groups,
            rf=self.cfg.search_hbm_ownership_rf,
            hot_rate=self.cfg.search_hbm_ownership_hot_rate,
            hedge_delay_ms=self.cfg.search_hedge_delay_ms)
        # heat promotions/demotions pre-stage or release residency
        # through THIS db's batcher (most recent TempoDB wins — the
        # REGISTRY idiom every process-wide layer above follows)
        _ownership.OWNERSHIP.set_change_hook(self._ownership_heat_change)
        if (self.cfg.search_offload_planner_enabled
                and not self.cfg.search_profiling_enabled):
            # the planner's device-side feed (device-probe rate, compile/
            # collective costs, h2d staging rate, jit shape-signature
            # set) arrives exclusively through the dispatch profiler —
            # with profiling off, decisions freeze at the one-shot
            # microbenchmark seed and every compile-site device
            # prediction keeps paying the compile penalty, biasing the
            # planner toward host forever. Results stay correct either
            # way, so warn rather than override the operator's config.
            log.warning(
                "search_offload_planner_enabled without "
                "search_profiling_enabled: the planner cannot "
                "self-calibrate (no dispatch-profiler feed) and will "
                "decide from its microbenchmark seed only; enable "
                "search_profiling_enabled for cost-model calibration")
        self._prewarm_stop = None  # Event cancelling the running prewarm
        self._prewarm_thread = None
        self._prewarm_atexit = False
        self._search_blocks: dict[str, BackendSearchBlock] = {}
        # header rollups cached separately from the container-holding
        # block objects: a header is ~1KB and every query's job planning
        # reads it for EVERY block — at 10K blocks the old shared 64-slot
        # LRU forced 10K disk reads + json parses per query (profiled as
        # the dominant serving cost, VERDICT r2 #1)
        self._headers: OrderedDict[str, dict] = OrderedDict()
        self._headers_max = 131_072
        # (epoch, jobs, fallback_metas) per tenant — see search()
        self._jobs_cache: dict[str, tuple] = {}
        # (epoch, jobs, fallback, missing_ranges, groups) per full job
        # signature — the SearchBlocksRequest protocol path's equivalent
        # (search_blocks)
        self._breq_jobs_cache = BoundedCache(32)
        self._search_lock = threading.Lock()
        # restartable host state: header snapshot + persistent XLA
        # compile cache. The snapshot's auto default lives under the WAL
        # dir — per-node durable storage that already must survive
        # restarts — in a SUBDIR because WAL replay deletes unknown
        # files in its root. The compile cache is placed by
        # utils.jaxenv alone (JAX_COMPILATION_CACHE_DIR, else a fixed
        # path in the checkout): a cache under a per-run WAL dir moves
        # every run and never hits.
        sd = self.cfg.host_state_dir
        self._state_dir = (os.path.join(wal_dir, "host-state")
                          if sd is None else (sd or None))
        if self._state_dir:
            from tempo_tpu.utils.jaxenv import enable_compile_cache

            enable_compile_cache()
            self._load_host_state()

    def _ensure_mesh(self) -> None:
        if self._mesh_resolved:
            return
        # serialized, flag set LAST: a concurrent first search must never
        # see a half-configured engine (unsharded batch → dist kernel)
        with self._search_lock:
            if self._mesh_resolved:
                return
            if self.cfg.auto_mesh:
                import jax

                if len(jax.devices()) > 1:
                    from tempo_tpu.parallel.mesh import make_mesh

                    self.mesh = make_mesh()
                    self.batcher.engine.mesh = self.mesh
                    self.batcher.engine.n_shards = int(self.mesh.devices.size)
            self._mesh_resolved = True

    def _plan(self, jobs: list) -> list:
        """The batcher's groups for `jobs`, never planned before the
        mesh is resolved: the group cap counts the mesh's devices."""
        self._ensure_mesh()
        return self.batcher.plan(jobs)

    # ------------------------------------------------------------------
    # Writer

    def complete_block(self, block: AppendBlock, search_entries=None) -> BlockMeta:
        """WAL block → immutable backend block (+ columnar search block).
        Reference flow: instance.CompleteBlock → tempodb.CompleteBlock...
        (SURVEY.md §3.2)."""
        codec = codec_for(block.meta.data_encoding)
        meta = BlockMeta(
            tenant_id=block.meta.tenant_id,
            block_id=block.meta.block_id,
            encoding=self.cfg.block_encoding,
            data_encoding=block.meta.data_encoding,
        )
        # stream through backend.append every complete_flush_bytes so a
        # max_block_bytes-sized completion never holds the whole compressed
        # block in RAM (reference streaming_block.go:27-155 flushes 30 MB)
        sb = StreamingBlock(meta, page_size=self.cfg.block_page_size,
                            backend=self.backend,
                            flush_size=self.cfg.complete_flush_bytes)
        try:
            for oid, obj in block.iterator():
                r = codec.fast_range(obj) or (0, 0)
                sb.add_object(oid, obj, r[0], r[1])
            out = sb.complete(self.backend)
        except BaseException:
            sb.abort()  # release the in-progress append before the retry
            raise
        if search_entries:
            write_search_block(self.backend, out, search_entries,
                               geometry=self.cfg.search_geometry,
                               encoding=self.cfg.search_encoding)
        self.blocklist.update(out.tenant_id, add=[out])
        return out

    def write_block_direct(self, tenant: str, objects, search_entries=None,
                           data_encoding: str = "v2") -> BlockMeta:
        """Write a complete block from (id, obj, start, end) tuples —
        used by tests/benchmarks and the compactor path."""
        meta = BlockMeta(tenant_id=tenant, encoding=self.cfg.block_encoding,
                         data_encoding=data_encoding)
        sb = StreamingBlock(meta, page_size=self.cfg.block_page_size,
                            backend=self.backend,
                            flush_size=self.cfg.complete_flush_bytes)
        try:
            for oid, obj, s, e in objects:
                sb.add_object(oid, obj, s, e)
            out = sb.complete(self.backend)
        except BaseException:
            sb.abort()
            raise
        if search_entries:
            write_search_block(self.backend, out, search_entries,
                               geometry=self.cfg.search_geometry,
                               encoding=self.cfg.search_encoding)
        self.blocklist.update(tenant, add=[out])
        return out

    # ------------------------------------------------------------------
    # Reader

    def poll(self) -> None:
        from tempo_tpu.observability.ingest_telemetry import TELEMETRY
        from tempo_tpu.robustness import FAULTS

        if FAULTS.active:
            FAULTS.hit("poll_error")  # a reader that stops seeing blocks
        t0 = time.perf_counter()
        with tracing.start_span("tempodb.Poll") as span:
            metas, compacted = self.poller.poll()
            self.blocklist.apply_poll_results(metas, compacted)
            span.set_attributes(
                tenants=len(metas),
                blocks=sum(len(ms) for ms in metas.values()))
        if TELEMETRY.enabled:
            # duration + per-tenant blocklist length + the freshness
            # gauge, and the flush->poll_visible pairing that closes the
            # push->searchable stage record (ingest_telemetry)
            TELEMETRY.record_poll(time.perf_counter() - t0, metas)
        # hot-tier eviction signal: blocks this poll made reader-visible
        # retire from the ingester's recently-flushed search leg (the
        # reader leg answers for them now — see live_tier.py)
        from tempo_tpu.search.live_tier import LIVE_TIER

        if LIVE_TIER.enabled:
            LIVE_TIER.mark_poll_visible(metas)
        live = {m.block_id for ms in metas.values() for m in ms}
        with self._search_lock:
            for bid in [b for b in self._search_blocks if b not in live]:
                del self._search_blocks[bid]
            for bid in [b for b in self._headers if b not in live]:
                del self._headers[bid]
        # cancel any running prewarm BEFORE invalidating: a thread
        # mid-_staged could otherwise re-insert a dead block's batch
        # after the invalidate and pin HBM until the next poll. The join
        # happens inside the new prewarm thread (or here if prewarm is
        # off) so poll itself stays fast.
        if self._prewarm_stop is not None:
            self._prewarm_stop.set()
        if self.cfg.search_prewarm_on_poll:
            self.batcher.cache.invalidate(live)
            self.prewarm(tenants=list(metas), reinvalidate=live)
        else:
            self.stop_prewarm()
            self.batcher.cache.invalidate(live)
        self.save_host_state()

    def prewarm(self, tenants: list[str], background: bool = True,
                reinvalidate: set | None = None) -> "threading.Thread | int":
        """Stage (host tier + HBM, up to budget) and compile-warm every
        tenant's batch groups so the first query after a poll pays
        neither staging nor the ~30s XLA compile (VERDICT r3 #2). Runs
        in a background thread by default; a newer poll's prewarm
        cancels the running one. `reinvalidate`: live block-id set to
        re-apply after the PREVIOUS prewarm thread has fully stopped —
        closes the window where its in-flight staging re-inserted a
        dead block's batch."""
        self._ensure_mesh()
        if self._prewarm_stop is not None:
            self._prewarm_stop.set()
        prev_thread = self._prewarm_thread
        stop = self._prewarm_stop = threading.Event()
        if not self._prewarm_atexit:
            # a daemon thread killed mid-device-op tears down the PJRT
            # runtime from under C++ and aborts the process; stop + join
            # (bounded) before interpreter teardown instead. Weakref so
            # the atexit registry does not pin this TempoDB (and its
            # multi-GB caches) for the life of the process.
            import atexit
            import weakref

            ref = weakref.ref(self)
            atexit.register(lambda: getattr(ref(), "stop_prewarm",
                                            lambda: None)())
            self._prewarm_atexit = True

        def run() -> int:
            from tempo_tpu.backend.raw import DoesNotExist

            if prev_thread is not None and prev_thread.is_alive():
                prev_thread.join()
            if reinvalidate is not None:
                self.batcher.cache.invalidate(reinvalidate)
            staged = 0
            for tenant in tenants:
                if stop.is_set():
                    break
                jobs = []
                for m in self.blocklist.metas(tenant):
                    try:
                        jobs.append(self._scan_job(m))
                    except DoesNotExist:
                        continue
                groups = self._plan(jobs)
                staged += self.batcher.prewarm(groups, stop=stop)
            # job planning above read EVERY live block's header — persist
            # the now-complete rollup set for the next process
            if not stop.is_set():
                self.save_host_state()
            return staged

        if not background:
            return run()
        t = threading.Thread(target=run, name="search-prewarm", daemon=True)
        t.start()
        self._prewarm_thread = t
        return t

    def stop_prewarm(self, timeout_s: float = 120.0) -> None:
        """Cancel a running background prewarm and wait for it to reach a
        safe point (between groups; an in-flight XLA compile must finish
        — it is not interruptible)."""
        if self._prewarm_stop is not None:
            self._prewarm_stop.set()
        t = self._prewarm_thread
        if t is not None and t.is_alive():
            t.join(timeout=timeout_s)

    def rebalance_ownership(self, members, self_id: str | None = None,
                            prestage: bool = True) -> dict:
        """Apply a fleet membership change to the HBM ownership map and
        treat the resulting evictions as a PLACEMENT change
        (docs/search-hbm-ownership.md): the generation bumps and only
        the moved groups change owner; groups this member no longer owns
        drop their HBM residency now (or at unpin, while a search holds
        them pinned); groups it newly owns pre-stage in the background
        from the cached job plans so the first owner-routed query after
        the rebalance pays no staging. Returns the rebalance summary
        (generation, moved groups, drops/deferrals)."""
        from tempo_tpu.search.ownership import OWNERSHIP

        moved = OWNERSHIP.set_members(members, self_id=self_id)
        out = {"generation": OWNERSHIP.generation, "moved_groups": moved}
        out.update(self.batcher.cache.rebalance_ownership())
        if prestage and OWNERSHIP.enabled:

            def _prestage() -> None:
                if not OWNERSHIP.enabled:
                    return
                gen = OWNERSHIP.generation
                with self._search_lock:
                    cached = list(self._jobs_cache.values())
                for hit in cached:
                    if OWNERSHIP.generation != gen:
                        return  # a newer rebalance superseded this one
                    groups = self._plan(list(hit[1]))
                    # prewarm() itself skips non-owned groups; no
                    # compile warm — the new owner wants residency, the
                    # jit cache is already hot for these shapes
                    self.batcher.prewarm(groups, warm_compile=False)

            threading.Thread(target=_prestage, name="ownership-prestage",
                             daemon=True).start()
        return out

    def _ownership_heat_change(self, group: int, direction: str,
                               replicas) -> None:
        """Heat-table promotion/demotion hook (runs on the ownership
        map's background thread, never a serving thread). A DEMOTION
        releases replica residency through the ordinary rebalance walk
        — owns_group stopped answering true for the dropped replica, so
        the deferred-evict path applies unchanged. A PROMOTION on a
        NEW replica (this member, not the primary) pre-stages the
        group's batches from the cached job plans so the frontend's
        hedged dispatch never races a cold stage — the hedge delay is
        p99-derived, and a cold H2D on the hedge path would lose every
        race it was meant to win."""
        from tempo_tpu.search.ownership import OWNERSHIP

        if direction == "down":
            self.batcher.cache.rebalance_ownership()
            return
        me = OWNERSHIP.self_id
        reps = tuple(replicas or ())
        if not reps or me not in reps or reps[0] == me:
            return  # not a replica here, or already the serving primary
        gen = OWNERSHIP.generation
        with self._search_lock:
            cached = list(self._jobs_cache.values())
        for hit in cached:
            if OWNERSHIP.generation != gen:
                return  # a rebalance superseded this promotion
            groups = self._plan(list(hit[1]))
            mine = [g for g in groups
                    if OWNERSHIP.group_of(str(g[0].key[0])) == group]
            if mine:
                self.batcher.prewarm(mine, warm_compile=False)

    @staticmethod
    def _include_block(m: BlockMeta, block_start: str, block_end: str,
                       start_s: int = 0, end_s: int = 0) -> bool:
        """Inclusion predicate (reference tempodb.go:492-520): block id in
        the [block_start, block_end] shard range, time windows overlap."""
        if block_start and m.block_id < block_start:
            return False
        if block_end and m.block_id > block_end:
            return False
        if start_s and m.end_time and m.end_time < start_s:
            return False
        if end_s and m.start_time and m.start_time > end_s:
            return False
        return True

    def find_trace_by_id(self, tenant: str, trace_id: bytes,
                         block_start: str = "", block_end: str = "") -> tuple[bytes | None, int]:
        """Fan out over candidate blocks; combine partial objects (the same
        trace can live in several blocks until compaction dedupes it).
        Returns (object bytes or None, failed_block_count)."""
        key = pad_trace_id(trace_id)
        metas = [m for m in self.blocklist.metas(tenant)
                 if self._include_block(m, block_start, block_end)]

        def job(m: BlockMeta):
            return BackendBlock(self.backend, m).find_by_id(key)

        # reference: store.Find span w/ inspected-block tags tempodb.go:291
        with tracing.start_span("tempodb.Find", tenant=tenant) as span:
            found, errors = run_jobs(metas, job, workers=self.cfg.pool_workers)
            span.set_attributes(candidate_blocks=len(metas),
                                failed_blocks=len(errors),
                                partials=len(found))
            if not found:
                return None, len(errors)
            codec = codec_for(metas[0].data_encoding if metas else "v2")
            return (found[0] if len(found) == 1
                    else codec.combine(*found)), len(errors)

    def _search_block_for(self, meta: BlockMeta) -> BackendSearchBlock:
        with self._search_lock:
            bsb = self._search_blocks.get(meta.block_id)
            if bsb is None:
                bsb = BackendSearchBlock(
                    self.backend, meta,
                    header=self._headers.get(meta.block_id))
                self._search_blocks[meta.block_id] = bsb
                # bounded HBM cache: evict oldest staged blocks
                while len(self._search_blocks) > self.cfg.search_cache_blocks:
                    self._search_blocks.pop(next(iter(self._search_blocks)))
            return bsb

    def _snapshot_path(self) -> str | None:
        return (os.path.join(self._state_dir, "search-headers.json.gz")
                if self._state_dir else None)

    def _load_host_state(self) -> None:
        """Load the header-rollup snapshot a previous process saved —
        job planning over a 10K-block tenant then costs zero backend
        header reads on the first query after a restart. Stale entries
        (blocks since deleted) are pruned by the next poll()."""
        import gzip
        import json as _json

        path = self._snapshot_path()
        if not path:
            return
        try:
            with open(path, "rb") as f:
                doc = _json.loads(gzip.decompress(f.read()))
            headers = doc["headers"] if doc.get("v") == 1 else {}
        except (OSError, EOFError, ValueError, KeyError, TypeError):
            return  # torn/corrupt snapshot: a cache, rebuild lazily
        with self._search_lock:
            for bid, hdr in headers.items():
                if isinstance(bid, str) and isinstance(hdr, dict):
                    self._headers[bid] = hdr
            while len(self._headers) > self._headers_max:
                self._headers.popitem(last=False)

    def save_host_state(self) -> None:
        """Snapshot the header cache next to the WAL (atomic rename).
        Called after every poll and prewarm; cheap (~100 KB gz at 10K
        blocks), so no debouncing needed."""
        import gzip
        import json as _json

        path = self._snapshot_path()
        if not path:
            return
        with self._search_lock:
            doc = {"v": 1, "headers": dict(self._headers)}
        try:
            os.makedirs(self._state_dir, exist_ok=True)
            blob = gzip.compress(
                _json.dumps(doc).encode(), compresslevel=1)
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except OSError:
            pass  # snapshot is an optimization, never a failure

    def _header_for(self, m: BlockMeta) -> dict:
        """Block search-header rollup, cached by block id (immutable once
        written). Raises DoesNotExist when the block has no container."""
        import json as _json

        from tempo_tpu.backend.types import NAME_SEARCH_HEADER

        with self._search_lock:
            hdr = self._headers.get(m.block_id)
            if hdr is not None:
                self._headers.move_to_end(m.block_id)
                return hdr
        hdr = _json.loads(self.backend.read(
            m.tenant_id, m.block_id, NAME_SEARCH_HEADER))
        with self._search_lock:
            self._headers[m.block_id] = hdr
            while len(self._headers) > self._headers_max:
                self._headers.popitem(last=False)
        return hdr

    def _scan_job(self, m: BlockMeta, start_page: int = 0,
                  pages: int | None = None) -> ScanJob:
        """A batcher job covering pages [start_page, start_page+pages) of
        the block's search container (whole block by default). Raises if
        the block has no search container (caller falls back to the
        trace-block proto scan). The block OBJECT (container holder) is
        only instantiated inside pages_fn — at staging time — so job
        planning over a 10K-block list touches nothing but the header
        cache."""
        hdr = self._header_for(m)
        total = hdr["n_pages"]
        n = total - start_page if pages is None else min(pages, total - start_page)
        n = max(0, n)
        if start_page == 0 and n == total:
            def pages_fn(self=self, m=m):
                return self._search_block_for(m).pages()
            n_entries = hdr["n_entries"]
        else:
            def pages_fn(self=self, m=m, s=start_page, c=n):
                return self._search_block_for(m).pages().slice_pages(s, c)
            # exact slice occupancy: entries fill pages densely in build
            # order, so page p holds min(E, total_entries - p*E) entries —
            # the batcher subtracts this from kernel counts when a sliced
            # job is pruned, and an estimate would corrupt the metrics
            E = hdr["entries_per_page"]
            n_entries = sum(
                max(0, min(E, hdr["n_entries"] - p * E))
                for p in range(start_page, start_page + n)
            )
        return ScanJob(
            key=(m.block_id, start_page, n),
            pages_fn=pages_fn, header=hdr, n_pages=n, n_entries=n_entries,
            geometry=(hdr["entries_per_page"], hdr["kv_per_entry"]),
            meta=m,
        )

    def search(self, tenant: str, req: tempopb.SearchRequest,
               results: SearchResults | None = None) -> SearchResults:
        """Search all (time-pruned) blocks of a tenant through the batched
        device engine — few kernel dispatches for many blocks, sharded
        over the mesh when one is configured — early-stopping at the
        result limit. Blocks without a search container fall back to the
        trace-block proto scan (reference backend_block.go:159-209)."""
        from tempo_tpu.backend.raw import DoesNotExist
        from tempo_tpu.search import query_stats

        results = results or SearchResults.for_request(req)
        self._ensure_mesh()
        qs = query_stats.begin(tenant, req)
        with obs.query_seconds.time(op="search"), \
                tracing.start_span("tempodb.Search", tenant=tenant) as span, \
                query_stats.activate(qs):
            # the job list is a function of the blocklist alone (time
            # pruning happens in the batcher's memoized header prune, so
            # stale-window blocks cost a cached skip, not staging): cache
            # it per (tenant, blocklist epoch) — rebuilding 10K ScanJobs
            # per query was a measured ~70 ms of pure host overhead
            epoch = self.blocklist.epoch()
            with self._search_lock:
                hit = self._jobs_cache.get(tenant)
            if hit is not None and hit[0] == epoch:
                jobs, fallback = hit[1], hit[2]
                if fallback:
                    # a DoesNotExist may have been transient (read-after-
                    # write lag): re-probe the few fallback blocks so one
                    # flake doesn't pin them to the slow path all epoch
                    promoted, still = [], []
                    for m in fallback:
                        try:
                            promoted.append(self._scan_job(m))
                        except DoesNotExist:
                            still.append(m)
                    if promoted:
                        jobs = jobs + promoted
                        fallback = still
                        with self._search_lock:
                            self._jobs_cache[tenant] = (epoch, jobs, fallback)
            else:
                jobs, fallback = [], []
                for m in self.blocklist.metas(tenant):
                    try:
                        jobs.append(self._scan_job(m))
                    except DoesNotExist:
                        fallback.append(m)  # no search container
                with self._search_lock:
                    self._jobs_cache[tenant] = (epoch, jobs, fallback)
            # len(jobs) in the plan key: fallback promotion grows the job
            # list within an epoch and the memoized plan must not drop it
            self.batcher.search(jobs, req, results,
                                plan_key=(tenant, epoch, len(jobs)),
                                tenant=tenant)
            if fallback and not results.complete:
                # container-less blocks have no header rollup to prune on
                # — apply the meta time filter here
                live = [m for m in fallback
                        if self._include_block(m, "", "", req.start, req.end)]
                results.metrics.skipped_blocks += len(fallback) - len(live)
                if qs is not None and len(fallback) > len(live):
                    qs.add_skip("time_range", len(fallback) - len(live))
                if live:
                    self._fallback_search(live, req, results)
            span.set_attributes(
                inspected_traces=results.metrics.inspected_traces,
                inspected_blocks=results.metrics.inspected_blocks,
                skipped_blocks=results.metrics.skipped_blocks,
                fallback_blocks=len(fallback))
            if qs is not None:
                self._finalize_query_stats(qs, req, results)
        obs.search_inspected.inc(results.metrics.inspected_traces, tenant=tenant)
        return results

    @staticmethod
    def _finalize_query_stats(qs, req, results) -> None:
        """Close the per-query record and surface it on the response:
        the device-seconds / device-bytes totals ALWAYS ride the
        SearchMetrics proto (they cross the frontend/querier process
        boundary and sum in the frontend merge); the full JSON
        breakdown rides only under the explain opt-in. finish() also
        publishes to the registry: per-tenant counters, the
        /debug/querystats ring, and the slow-query log."""
        import json as _json

        d = qs.finish()
        m = results.metrics
        m.device_seconds += d["device_seconds"]
        m.inspected_bytes_device += int(qs.bytes_device)
        if getattr(req, "explain", False):
            m.query_stats_json = _json.dumps(d, separators=(",", ":"),
                                             sort_keys=True)

    def _fallback_search(self, metas: list[BlockMeta], req,
                         results: SearchResults) -> None:
        """Whole-block trace proto scan for blocks lacking search data:
        decode every object and evaluate the request against the full
        proto (reference encoding/v2/backend_block.go:159-209 +
        pkg/model/trace/matches.go:33-184). Always whole-block: search
        page ranges address the container's page space, not this one."""
        from tempo_tpu.model.matches import matches as proto_matches
        from tempo_tpu.model.matches import trace_search_metadata
        from tempo_tpu.search import query_stats

        qs = query_stats.current()
        t0 = time.perf_counter()
        try:
            for m in metas:
                block = BackendBlock(self.backend, m)
                codec = codec_for(m.data_encoding)
                obs.fallback_scans.inc(tenant=m.tenant_id)
                results.metrics.inspected_blocks += 1
                nbytes = block.bytes_in_pages(0, None)
                results.metrics.inspected_bytes += nbytes
                if qs is not None:
                    # whole-block proto decode: pure HOST work
                    qs.add_inspected(blocks=1, nbytes=nbytes,
                                     placement="host")
                for oid, obj in block.iter_objects():
                    results.metrics.inspected_traces += 1
                    trace = codec.prepare_for_read(obj)
                    if proto_matches(trace, req):
                        results.add(trace_search_metadata(oid, trace))
                    if results.complete:
                        return
        finally:
            if qs is not None:
                qs.add_stage("fallback_scan", time.perf_counter() - t0)

    def search_block(self, req: tempopb.SearchBlockRequest) -> SearchResults:
        """One search job (the SearchBlockRequest protocol unit). The block
        meta travels in the request, as in the reference querier
        (internalSearchBlock rebuilding BlockMeta from params); start_page/
        pages_to_search scope the job to a page range of the search
        container (reference searchsharding.go page math). Runs through
        the batcher so repeated jobs hit the staged cache and shard over
        the mesh."""
        meta = BlockMeta(
            tenant_id=req.tenant_id, block_id=req.block_id,
            encoding=req.encoding or "zstd", version=req.version or "vT1",
            data_encoding=req.data_encoding or "v2",
            start_time=req.start_time, end_time=req.end_time,
        )
        from tempo_tpu.backend.raw import DoesNotExist
        from tempo_tpu.search import query_stats

        results = SearchResults.for_request(req.search_req)
        self._ensure_mesh()
        qs = query_stats.begin(req.tenant_id, req.search_req)
        with query_stats.activate(qs):
            start = req.start_page
            count = req.pages_to_search or None
            try:
                job = self._scan_job(meta, start, count)
            except DoesNotExist:
                # No search container. Page ranges address CONTAINER
                # pages, a different page space from trace-block pages,
                # so a range is meaningless here: the start_page==0 job
                # scans the whole trace block once; sibling range jobs
                # contribute nothing (coverage stays exactly-once across
                # the job set).
                sr = req.search_req
                if start == 0:
                    if self._include_block(meta, "", "", sr.start, sr.end):
                        self._fallback_search([meta], sr, results)
                    else:
                        results.metrics.skipped_blocks += 1
                        if qs is not None:
                            qs.add_skip("time_range")
                if qs is not None:
                    self._finalize_query_stats(qs, req.search_req, results)
                return results
            if job.n_pages > 0:
                self.batcher.search([job], req.search_req, results)
            if qs is not None:
                self._finalize_query_stats(qs, req.search_req, results)
        return results

    def search_meta(self, meta: BlockMeta, req: tempopb.SearchRequest,
                    results: SearchResults) -> None:
        """One whole block, known by its meta and not (yet) by the
        blocklist — the ingester's recently-completed leg — searched
        through the batcher like every other: a one-block batch. Raises
        DoesNotExist where the block has no search container.

        This leg's hits overlap the blocklist's once a poll has seen
        the block, and dedupe there by trace id; ?agg= counts could
        not, so the leg never aggregates."""
        from tempo_tpu.search.analytics import AGG_QUERY_TAG

        if AGG_QUERY_TAG in req.tags:
            plain = tempopb.SearchRequest()
            plain.CopyFrom(req)
            del plain.tags[AGG_QUERY_TAG]
            req = plain
        self._ensure_mesh()
        job = self._scan_job(meta)
        if job.n_pages > 0:
            self.batcher.search([job], req, results)

    def search_blocks(self, breq: tempopb.SearchBlocksRequest) -> SearchResults:
        """A batched job request (many page-range jobs, one kernel
        dispatch per geometry group) — the TPU-native protocol unit the
        frontend emits. Jobs whose blocks lack a search container run the
        proto fallback scan after the batched pass.

        The ScanJob list and the batcher's group plan are memoized on the
        request's job signature: the frontend re-sends the same job set
        every query over a stable blocklist, and rebuilding + re-sorting
        10K jobs per request is the kind of O(blocks) host cost the north
        star forbids (VERDICT r3 #1)."""
        from tempo_tpu.search import query_stats

        results = SearchResults.for_request(breq.search_req)
        self._ensure_mesh()
        qs = query_stats.begin(breq.tenant_id, breq.search_req)
        with query_stats.activate(qs):
            self._search_blocks_impl(breq, results, qs)
            if qs is not None:
                self._finalize_query_stats(qs, breq.search_req, results)
        return results

    def _search_blocks_impl(self, breq, results, qs) -> None:
        from tempo_tpu.backend.raw import DoesNotExist

        # full-fidelity key (every job field that shapes the ScanJob) used
        # AS the map key: a bare hash() would let a collision or an
        # encoding/version-only difference silently serve another
        # request's jobs; tuple equality removes both
        sig = (breq.tenant_id,
               tuple((j.block_id, j.start_page, j.pages_to_search,
                      j.encoding, j.version, j.data_encoding)
                     for j in breq.jobs))
        # the cached plan is a function of the group cap too (the mesh,
        # resolved above, sets it): one generation with the blocklist's
        epoch = (self.blocklist.epoch(), self.batcher.group_cap())
        hit = self._breq_jobs_cache.get(sig)
        if hit is not None and hit[0] == epoch:
            jobs, fallback, missing, groups = hit[1], hit[2], hit[3], hit[4]
            if fallback or missing:
                # a DoesNotExist may have been transient (read-after-write
                # lag): re-probe so one flake doesn't pin a block to the
                # slow proto scan — or a dropped page-range job to
                # nothing — for the whole epoch (mirrors search()'s
                # fallback promotion)
                promoted = []
                still_fb, still_miss = [], []
                for meta in fallback:
                    try:
                        promoted.append(self._scan_job(meta))
                    except DoesNotExist:
                        still_fb.append(meta)
                for meta, sp, pp in missing:
                    try:
                        job = self._scan_job(meta, sp, pp or None)
                        if job.n_pages > 0:
                            promoted.append(job)
                    except DoesNotExist:
                        still_miss.append((meta, sp, pp))
                if promoted:
                    jobs = jobs + promoted
                    fallback, missing = still_fb, still_miss
                    groups = self._plan(jobs)
                    self._breq_jobs_cache.put(
                        sig, (epoch, jobs, fallback, missing, groups))
        else:
            jobs, fallback, missing = [], [], []
            for j in breq.jobs:
                meta = BlockMeta(
                    tenant_id=breq.tenant_id, block_id=j.block_id,
                    encoding=j.encoding or "zstd", version=j.version or "vT1",
                    data_encoding=j.data_encoding or "v2",
                    start_time=j.start_time, end_time=j.end_time,
                )
                try:
                    job = self._scan_job(meta, j.start_page,
                                         j.pages_to_search or None)
                    # zero-page jobs (stale meta, start_page past the
                    # container) would stage an empty batch — drop them, as
                    # search_block does
                    if job.n_pages > 0:
                        jobs.append(job)
                except DoesNotExist:
                    # container missing: only the 0-start job scans (whole
                    # trace block, its own page space) — see search_block;
                    # range jobs are remembered for promotion, not lost
                    if j.start_page == 0:
                        fallback.append(meta)
                    else:
                        missing.append((meta, j.start_page,
                                        j.pages_to_search))
            # the group plan is a pure function of the job list — cached
            # WITH it, so the per-query batcher path neither re-sorts 10K
            # jobs nor hashes a plan key
            groups = self._plan(jobs)
            self._breq_jobs_cache.put(
                sig, (epoch, jobs, fallback, missing, groups))
        self.batcher.search(jobs, breq.search_req, results, groups=groups,
                            tenant=breq.tenant_id)
        # container-less blocks have no header rollup: apply the meta
        # window carried in the job before paying a whole-block proto
        # decode (same gate as search(); the frontend no longer
        # pre-filters metas by window)
        sr = breq.search_req
        for meta in fallback:
            if results.complete:
                break
            if not self._include_block(meta, "", "", sr.start, sr.end):
                results.metrics.skipped_blocks += 1
                if qs is not None:
                    qs.add_skip("time_range")
                continue
            self._fallback_search([meta], sr, results)

    # ------------------------------------------------------------------
    # Compactor

    def compact_tenant_once(self, tenant: str, now_s: int | None = None) -> BlockMeta | None:
        from tempo_tpu.observability.ingest_telemetry import TELEMETRY

        now_s = int(time.time()) if now_s is None else now_s
        metas = self.blocklist.metas(tenant)
        # one grouping pass serves both the job pick and the backlog
        # gauge — _groups is O(blocks) and this runs per tenant per tick
        groups = self.selector._groups(metas, now_s)  # noqa: SLF001
        inputs = self.selector.blocks_to_compact(metas, now_s,
                                                 groups=groups)
        if TELEMETRY.enabled:
            # input backlog BEFORE the run: bytes sitting in compactable
            # groups — a gauge that keeps climbing means the compactor
            # loop can't keep up with the write rate
            n_blocks, n_bytes = self.selector.outstanding(metas, now_s,
                                                          groups=groups)
            TELEMETRY.record_compaction_backlog(tenant, n_bytes, n_blocks)
        if not inputs:
            return None
        t0 = time.perf_counter()
        with tracing.start_span("tempodb.Compact", tenant=tenant) as span:
            new_meta = compact_blocks(
                self.backend, tenant, inputs,
                page_size=self.cfg.block_page_size,
                search_geometry=self.cfg.search_geometry,
                search_encoding=self.cfg.search_encoding,
                flush_size=self.cfg.compaction_flush_bytes)
            span.set_attributes(inputs=len(inputs),
                                input_bytes=sum(m.size for m in inputs),
                                out_block=new_meta.block_id)
        if TELEMETRY.enabled:
            TELEMETRY.record_compaction_run(time.perf_counter() - t0)
        obs.compactions.inc(tenant=tenant)
        from tempo_tpu.backend.types import CompactedBlockMeta

        self.blocklist.update(
            tenant, add=[new_meta], remove=inputs,
            add_compacted=[CompactedBlockMeta.from_meta(m) for m in inputs],
        )
        return new_meta

    def retain_tenant(self, tenant: str, now_s: int | None = None) -> tuple[int, int]:
        now_s = int(time.time()) if now_s is None else now_s
        return apply_retention(
            self.backend, self.blocklist, tenant, now_s,
            retention_s=self.cfg.retention_s,
            compacted_retention_s=self.cfg.compacted_retention_s,
        )
