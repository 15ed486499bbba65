"""YAML config loading with env substitution and footgun warnings.

Role-equivalent to the reference's cmd/tempo config load (main.go:117-175
``-config.file`` + ``-config.expand-env``) and CheckConfig warnings
(app.go:136-164). The YAML tree mirrors AppConfig/TempoDBConfig fields:

    server:
      http_port: 3200
      grpc_port: 9095
    multitenancy_enabled: true
    storage:
      backend: local            # local | memory
      local: {path: /var/tempo/blocks}
      wal_dir: /var/tempo/wal
      block_encoding: zstd
      search_encoding: zstd
    ingester:
      n_ingesters: 1
      replication_factor: 1
      write_quorum: majority    # or "one" (RF=2 eventual consistency)
    querier:
      external_endpoints: []    # serverless search-worker URLs
    compactor: {window_s: 3600, max_inputs: 8}
    retention: {block_s: 1209600, compacted_s: 3600}
    overrides:
      defaults: {ingestion_rate_bytes: 15000000, ...}
      per_tenant: {tenant-a: {max_live_traces: 100000}}
"""

from __future__ import annotations

import os
import re

import yaml

from tempo_tpu.db import TempoDBConfig
from tempo_tpu.modules import AppConfig, Limits
from tempo_tpu.modules.frontend import FrontendConfig

_ENV_RE = re.compile(r"\$\{(\w+)(?::([^}]*))?\}")


def expand_env(text: str) -> str:
    """${VAR} / ${VAR:default} substitution (reference -config.expand-env)."""
    return _ENV_RE.sub(
        lambda m: os.environ.get(m.group(1), m.group(2) or ""), text
    )


def load_config(path: str | None = None, text: str | None = None) -> tuple[AppConfig, dict]:
    if text is None:
        text = open(path).read() if path else "{}"
    doc = yaml.safe_load(expand_env(text)) or {}

    # `or {}` throughout: a bare section key with its children commented
    # out parses to None, which must mean "all defaults", not a crash
    storage = doc.get("storage") or {}
    ingester = doc.get("ingester") or {}
    compactor = doc.get("compactor") or {}
    retention = doc.get("retention") or {}
    overrides = doc.get("overrides") or {}
    frontend_doc = doc.get("frontend") or {}
    querier_doc = doc.get("querier") or {}

    # self_tracing passes through to init_tracing as a dict, but the
    # dogfood knobs are read (and type-normalized) HERE explicitly so
    # the yaml-knob drift catalog pins them to documented rows
    # (docs/configuration.md; tests/test_config_docs.py)
    self_tracing = dict(doc.get("self_tracing") or {})
    self_tracing["selftrace_ingest_enabled"] = bool(
        self_tracing.get("selftrace_ingest_enabled", False))
    self_tracing["selftrace_flight_recorder_max"] = int(
        self_tracing.get("selftrace_flight_recorder_max", 32))

    db = TempoDBConfig(
        block_encoding=storage.get("block_encoding", "zstd"),
        wal_encoding=storage.get("wal_encoding", "auto"),
        search_encoding=storage.get("search_encoding", "zstd"),
        compaction_window_s=compactor.get("window_s", 3600),
        compaction_max_inputs=compactor.get("max_inputs", 8),
        retention_s=retention.get("block_s", 14 * 24 * 3600),
        compacted_retention_s=retention.get("compacted_s", 3600),
        blocklist_poll_s=storage.get("blocklist_poll_s", 30),
        # serving-tier budgets the runbook tells operators to raise
        # under staging pressure (/debug/scan)
        search_batch_cache_bytes=storage.get(
            "search_batch_cache_bytes", 4 << 30),
        search_host_cache_bytes=storage.get("search_host_cache_bytes"),
        search_prewarm_on_poll=storage.get("search_prewarm_on_poll", False),
        # cross-request query coalescing (docs/search-coalescing.md)
        search_coalesce_window_s=storage.get(
            "search_coalesce_window_s", 0.003),
        search_coalesce_max_queries=storage.get(
            "search_coalesce_max_queries", 8),
        # device-resident dictionary probe threshold
        # (docs/search-dict-probe.md); absent/null = library default
        # (50k distinct values), <= 0 = host-only probing
        search_device_probe_min_vals=storage.get(
            "search_device_probe_min_vals"),
        # dispatch profiler (docs/observability.md): per-dispatch stage
        # telemetry + /debug/profile; false is a true noop on the
        # dispatch hot path
        search_profiling_enabled=storage.get(
            "search_profiling_enabled", True),
        search_profiling_fence=storage.get(
            "search_profiling_fence", False),
        search_profiling_ring=storage.get("search_profiling_ring", 256),
        # per-query execution inspector (docs/search-query-stats.md):
        # per-tenant device-seconds accounting, slow-query log,
        # /debug/querystats, ?explain=1; false is a true noop on the
        # search path
        search_query_stats_enabled=storage.get(
            "search_query_stats_enabled", True),
        search_slow_query_log_s=storage.get(
            "search_slow_query_log_s", 10.0),
        search_query_stats_ring=storage.get(
            "search_query_stats_ring", 256),
        # adaptive host/device offload planner
        # (docs/search-offload-planner.md): cost-model placement of the
        # dictionary prefilter above the device-probe floor; false
        # (default) keeps the static threshold behavior exactly
        search_offload_planner_enabled=storage.get(
            "search_offload_planner_enabled", False),
        search_offload_planner_ewma=storage.get(
            "search_offload_planner_ewma", 0.25),
        search_offload_planner_ring=storage.get(
            "search_offload_planner_ring", 256),
        # hot-tier live search (docs/search-live-tail.md): in-flight
        # traces kernel-scan at query time and tail subscriptions
        # evaluate per push; false (default) is a true noop — live/WAL
        # search keeps the per-entry host walk byte-identically
        search_live_tier_enabled=storage.get(
            "search_live_tier_enabled", False),
        search_live_tier_max_entries=storage.get(
            "search_live_tier_max_entries", 4096),
        search_live_tail_max_subscriptions=storage.get(
            "search_live_tail_max_subscriptions", 16),
        # device-side aggregate analytics (docs/search-analytics.md):
        # batched RED/service-graph reductions on the generator feed +
        # query-time ?agg=; false (default) is a true noop and the
        # drained series are byte-identical either way
        search_analytics_enabled=storage.get(
            "search_analytics_enabled", False),
        search_analytics_min_rows=storage.get(
            "search_analytics_min_rows", 64),
        # packed HBM residency (docs/search-packed-residency.md):
        # bit-width-adaptive staged columns + in-kernel unpack; false
        # (default) is a true noop and byte-identical either way
        search_packed_residency=storage.get(
            "search_packed_residency", False),
        # structural query engine (docs/search-structural-queries.md):
        # the ?q= IR compiled onto the fused scan kernels; false
        # (default) is a true noop on the legacy search path
        search_structural_enabled=storage.get(
            "search_structural_enabled", False),
        search_structural_max_spans=storage.get(
            "search_structural_max_spans", 512),
        search_structural_max_span_kvs=storage.get(
            "search_structural_max_span_kvs", 16),
        search_structural_stack_enabled=storage.get(
            "search_structural_stack_enabled", False),
        search_structural_shard_spans=storage.get(
            "search_structural_shard_spans", False),
        # shape-bucketed cross-plan stacking + remainder-shard staging
        # (docs/search-structural-queries.md#shape-bucketed-stacking):
        # both false (default) are true noops and byte-identical on
        search_structural_bucket_enabled=storage.get(
            "search_structural_bucket_enabled", False),
        search_structural_bucket_max_nodes=storage.get(
            "search_structural_bucket_max_nodes", 16),
        search_structural_remainder_pages=storage.get(
            "search_structural_remainder_pages", False),
        # owner-routed HBM (docs/search-hbm-ownership.md): consistent-
        # hash block-group ownership across the fleet; false (default)
        # is a true noop, members/self auto-derive from the multihost
        # env contract when left empty
        search_hbm_ownership_enabled=storage.get(
            "search_hbm_ownership_enabled", False),
        search_hbm_ownership_members=storage.get(
            "search_hbm_ownership_members", ""),
        search_hbm_ownership_self=storage.get(
            "search_hbm_ownership_self", ""),
        search_hbm_ownership_groups=storage.get(
            "search_hbm_ownership_groups", 64),
        # heat-adaptive replication + hedged dispatch
        # (docs/search-hbm-ownership.md#replication-heat-and-hedged-
        # dispatch): rf=1 (default) keeps single-owner placement bit
        # for bit — heat table, replica lookups and hedge timer are
        # each one attribute read
        search_hbm_ownership_rf=storage.get(
            "search_hbm_ownership_rf", 1),
        search_hbm_ownership_hot_rate=storage.get(
            "search_hbm_ownership_hot_rate", 50.0),
        search_hedge_delay_ms=storage.get(
            "search_hedge_delay_ms", 0.0),
        # robustness (docs/robustness.md): device dispatch watchdog,
        # collective-lock bound, request deadlines, circuit breaker,
        # fault-injection arming. Breaker off + faults disarmed is a
        # true noop on the dispatch path.
        search_device_dispatch_timeout_s=storage.get(
            "search_device_dispatch_timeout_s", 30.0),
        search_dispatch_lock_timeout_s=storage.get(
            "search_dispatch_lock_timeout_s", 60.0),
        search_request_timeout_s=storage.get(
            "search_request_timeout_s", 0.0),
        search_breaker_enabled=storage.get("search_breaker_enabled", True),
        search_breaker_fault_threshold=storage.get(
            "search_breaker_fault_threshold", 3),
        search_breaker_window_s=storage.get(
            "search_breaker_window_s", 30.0),
        search_breaker_cooldown_s=storage.get(
            "search_breaker_cooldown_s", 5.0),
        robustness_faults=storage.get("robustness_faults", ""),
        # restartable host state (header snapshot under this directory
        # + persistent XLA compile cache where utils.jaxenv places it);
        # absent = auto (<wal_dir>/host-state), "" = off
        host_state_dir=storage.get("host_state_dir"),
    )
    cfg = AppConfig(
        backend={
            "backend": storage.get("backend", "local"),
            "local": storage.get("local", {"path": "./tempo-blocks"}),
            "s3": storage.get("s3", {}),
            "gcs": storage.get("gcs", {}),
            "azure": storage.get("azure", {}),
        },
        cache=storage.get("cache", {}),
        wal_dir=storage.get("wal_dir", "./tempo-wal"),
        n_ingesters=ingester.get("n_ingesters", 1),
        replication_factor=ingester.get("replication_factor", 1),
        write_quorum=ingester.get("write_quorum", "majority"),
        external_endpoints=querier_doc.get("external_endpoints", []),
        # frontend: {query_shards, max_concurrent_jobs, retries,
        # tolerate_failed_blocks, max_outstanding_per_tenant,
        # target_bytes_per_job, batch_jobs_per_request} — sharding/queue
        # knobs (reference query_frontend block)
        frontend=FrontendConfig(**{
            k: v for k, v in frontend_doc.items()
            if k in FrontendConfig.__dataclass_fields__
        }),
        frontend_worker_parallelism=querier_doc.get(
            "frontend_worker_parallelism", 2),
        frontend_grpc_max_workers=frontend_doc.get("grpc_max_workers", 256),
        flush_tick_s=ingester.get("flush_tick_s", 10.0),
        # write-path telemetry + freshness canary
        # (docs/observability.md write-path section): telemetry-off is a
        # true noop on the ingest path; the canary is opt-in because it
        # writes real (tiny) blocks into its tenant every interval
        ingest_telemetry_enabled=ingester.get(
            "ingest_telemetry_enabled", True),
        ingest_slow_flush_log_s=ingester.get(
            "ingest_slow_flush_log_s", 30.0),
        ingest_canary_enabled=ingester.get("ingest_canary_enabled", False),
        ingest_canary_interval_s=ingester.get(
            "ingest_canary_interval_s", 30.0),
        ingest_canary_tenant=ingester.get("ingest_canary_tenant", "canary"),
        poll_tick_s=storage.get("poll_tick_s", 30.0),
        compaction_tick_s=compactor.get("tick_s", 30.0),
        db=db,
        limits=Limits(**{
            k: v for k, v in overrides.get("defaults", {}).items()
            if k in Limits.__dataclass_fields__
        }),
        per_tenant_overrides=overrides.get("per_tenant", {}),
        self_tracing=self_tracing,
        metrics_generator=doc.get("metrics_generator", {}),
        receivers=doc.get("distributor", {}).get("receivers", {}),
    )
    server = doc.get("server", {})
    runtime = {
        "http_port": server.get("http_port", 3200),
        "grpc_port": server.get("grpc_port", 9095),
        # jaeger agent UDP ingest (compact/binary thrift emitBatch);
        # 0/absent = disabled, 6831 is the jaeger default
        "jaeger_agent_port": server.get("jaeger_agent_port", 0),
        # /debug/* (stack dumps, scan internals) off by default on the
        # serving port; flip on for a triage session or bind a separate
        # admin ingress to a debug-enabled target (ADVICE r4)
        "debug_endpoints": server.get("debug_endpoints", False),
        "multitenancy": doc.get("multitenancy_enabled", True),
        # memberlist: {bind: "host:port", join: [addr, ...], advertise_host,
        # gossip_interval_s, suspect_timeout_s} — multi-process gossip
        "memberlist": doc.get("memberlist", {}),
        "instance_id": doc.get("instance_id", ""),
        # multi-host mesh: {coordinator: "host:port", num_processes,
        # process_id, cpu_devices_per_host} — env-substitutable
        # (${TEMPO_PROCESS_ID}); empty/absent = single host. A v5e-64
        # (BASELINE config 5) is coordinator + num_processes: 16 (4 chips
        # per host), the scan mesh axis spanning all 64 chips.
        "distributed": doc.get("distributed", {}),
        "warnings": check_config(cfg, doc),
    }
    return cfg, runtime


def check_config(cfg: AppConfig, doc: dict) -> list[str]:
    warnings = []
    if cfg.replication_factor > cfg.n_ingesters:
        warnings.append(
            f"replication_factor ({cfg.replication_factor}) exceeds ingester "
            f"count ({cfg.n_ingesters}); writes will fail quorum"
        )
    if cfg.db.compacted_retention_s == 0:
        warnings.append(
            "compacted block retention is 0: compacted blocks are deleted "
            "immediately, racing in-flight queries"
        )
    if cfg.backend.get("backend") == "memory":
        warnings.append("memory backend: data does not survive restarts")
    return warnings
