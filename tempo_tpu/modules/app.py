"""Single-binary app wiring.

Role-equivalent to the reference's cmd/tempo/app (modules.go dependency
DAG, target selection): builds the full pipeline in one process —
distributor → ring → N ingesters → shared TempoDB ← queriers ←
frontend — plus the maintenance loops (flush sweep, blocklist poll,
compaction, retention) exposed as explicit tick methods so tests and
operators drive them deterministically; `run_maintenance` starts the
background threads for real deployments.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

from tempo_tpu.backend import open_backend
from tempo_tpu.db import TempoDB, TempoDBConfig
from tempo_tpu.observability.log import get_logger
from .distributor import Distributor
from .frontend import QueryFrontend, FrontendConfig
from .generator import MetricsGenerator
from .ingester import FlushIncompleteError, Ingester
from .overrides import Overrides, Limits
from .querier import Querier
from .ring import Ring


log = get_logger("tempo_tpu.app")


@dataclass
class AppConfig:
    backend: dict = field(default_factory=lambda: {"backend": "memory"})
    cache: dict = field(default_factory=dict)  # {"cache": "lru|memcached|redis|none", ...}
    wal_dir: str = "./wal"
    n_ingesters: int = 1
    n_queriers: int = 1
    replication_factor: int = 1
    db: TempoDBConfig = field(default_factory=TempoDBConfig)
    frontend: FrontendConfig = field(default_factory=FrontendConfig)
    limits: Limits = field(default_factory=Limits)
    per_tenant_overrides: dict = field(default_factory=dict)
    write_quorum: str = "majority"  # or "one" (RF=2 eventual consistency)
    external_endpoints: list = field(default_factory=list)  # serverless workers
    flush_tick_s: float = 10.0
    poll_tick_s: float = 30.0
    compaction_tick_s: float = 30.0
    # self_tracing: {enabled, exporter: self|otlp, endpoint, tenant,
    # sample_ratio} — the framework traces itself (observability/tracing)
    self_tracing: dict = field(default_factory=dict)
    # metrics_generator: {remote_write: {url, headers, interval_s,
    # external_labels}, spool_dir} — prometheus remote-write shipping
    metrics_generator: dict = field(default_factory=dict)
    # receivers: {kafka: {brokers, topic, group_id, encoding, ...},
    # pubsub_lite: {topic, subscription, ...}} — pull-based ingest
    # (push receivers — OTLP gRPC/HTTP, Zipkin, Jaeger — live on the
    # server ports and need no config here)
    receivers: dict = field(default_factory=dict)
    # streams each querier opens per discovered query-frontend for pull
    # dispatch (reference querier.frontend_worker parallelism)
    frontend_worker_parallelism: int = 2
    # write-path telemetry (observability/ingest_telemetry.py): stage
    # histograms push->searchable, freshness/backlog gauges, slow-flush
    # log, /debug/ingest. False is a true noop on the ingest path —
    # record sites branch out on one attribute read, ingest output is
    # byte-identical (tests/test_ingest_telemetry.py
    # test_telemetry_off_is_byte_identical_on_the_wal)
    ingest_telemetry_enabled: bool = True
    # slow-flush JSON log threshold (seconds): a successful block
    # completion slower than this emits ONE structured line on
    # tempo_tpu.slowflush (token-bucket rate-limited per tenant under a
    # global ceiling, the slow-query log's idiom); <= 0 disables the
    # line — tempo_ingester_slow_flushes_total still counts every one
    ingest_slow_flush_log_s: float = 30.0
    # synthetic freshness canary: every interval, push one tagged trace
    # and poll BACKEND search until it is visible, exporting measured
    # push->searchable as tempo_ingest_canary_freshness_seconds (+ a
    # failure counter past the deadline). The black-box complement to
    # the white-box stage metrics — a wedged flush/poll loop looks
    # "idle" to each stage individually but times the canary out. Off
    # by default: it writes real (tiny) blocks into its tenant.
    ingest_canary_enabled: bool = False
    ingest_canary_interval_s: float = 30.0
    ingest_canary_tenant: str = "canary"
    # gRPC executor threads on the query-frontend: every pull stream
    # PARKS one thread for its lifetime, so size this above queriers ×
    # parallelism + unary headroom — a starved stream is silent
    frontend_grpc_max_workers: int = 256


class App:
    def __init__(self, cfg: AppConfig | None = None):
        self.cfg = cfg or AppConfig()
        self.backend = open_backend(self.cfg.backend)
        if self.cfg.cache:
            from tempo_tpu.backend.cache import CachedBackend
            from tempo_tpu.backend.netcache import open_cache
            cache = open_cache(self.cfg.cache)
            if cache is not None:
                self.backend = CachedBackend(self.backend, cache=cache)
        self.overrides = Overrides(self.cfg.limits,
                                   self.cfg.per_tenant_overrides)
        self.ring = Ring(replication_factor=self.cfg.replication_factor)

        self.ingesters: dict[str, Ingester] = {}
        self.dbs: list[TempoDB] = []
        for i in range(self.cfg.n_ingesters):
            iid = f"ingester-{i}"
            db = TempoDB(self.backend, f"{self.cfg.wal_dir}/{iid}", self.cfg.db)
            self.dbs.append(db)
            self.ingesters[iid] = Ingester(db, self.overrides, instance_id=iid)
            self.ring.register(iid)

        # queriers share one reader db (blocklist + staged-block cache)
        self.reader_db = TempoDB(self.backend, f"{self.cfg.wal_dir}/querier",
                                 self.cfg.db)
        self.generator = MetricsGenerator()
        self.remote_write = None
        gen_cfg = self.cfg.metrics_generator or {}
        rw = gen_cfg.get("remote_write") or {}
        if rw.get("url"):
            from .remote_write import RemoteWriteShipper
            self.remote_write = RemoteWriteShipper(
                self.generator, rw["url"],
                spool_dir=gen_cfg.get("spool_dir",
                                      f"{self.cfg.wal_dir}/remote-write"),
                interval_s=float(rw.get("interval_s", 15.0)),
                external_labels=rw.get("external_labels", {}),
                headers=rw.get("headers", {}),
            )
        self.distributor = Distributor(self.ring, self.ingesters, self.overrides,
                                       forwarder=self.generator.forward,
                                       write_quorum=self.cfg.write_quorum)
        self.queriers = [
            Querier(self.reader_db, self.ring, self.ingesters, self.overrides,
                    external_endpoints=self.cfg.external_endpoints)
            for _ in range(self.cfg.n_queriers)
        ]
        self.frontend = QueryFrontend(self.queriers, self.cfg.frontend)
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._receivers: list = []
        # self-tracing ("tempo traces tempo"): export into our own
        # distributor by default, or OTLP/HTTP out to a collector
        from tempo_tpu.observability import tracing
        self.tracer = tracing.init_tracing(self.cfg.self_tracing,
                                           push=self.push)
        # build identity: the constant-1 gauge whose labels say WHAT is
        # running (set once here; /status re-evaluates live)
        from tempo_tpu.observability import metrics as obs
        from tempo_tpu.observability import profile
        obs.build_info.set(1, **profile.build_info())
        # write-path telemetry + freshness canary (process-wide sink,
        # the profiler idiom: the most recent App's config wins)
        from tempo_tpu.observability import ingest_telemetry
        ingest_telemetry.configure(
            enabled=self.cfg.ingest_telemetry_enabled,
            slow_flush_log_s=self.cfg.ingest_slow_flush_log_s)
        self.canary = None
        if self.cfg.ingest_canary_enabled:
            # the canary searches the READER db, not the frontend: the
            # frontend's ingester leg would see the live trace instantly
            # and mask the very flush/poll wedge the probe exists for
            self.canary = ingest_telemetry.IngestCanary(
                push_fn=self.push,
                search_fn=self.reader_db.search,
                tenant=self.cfg.ingest_canary_tenant,
                interval_s=self.cfg.ingest_canary_interval_s)
        ingest_telemetry.TELEMETRY.canary = self.canary

    # ---- public API surface (what api/http.py routes onto) ----

    def push(self, tenant: str, batches) -> None:
        self.distributor.push_batches(tenant, batches)

    def find_trace(self, tenant: str, trace_id: bytes):
        return self.frontend.find_trace_by_id(tenant, trace_id)

    def search(self, tenant: str, req, on_progress=None):
        return self.frontend.search(tenant, req, on_progress=on_progress)

    def tail_subscribe(self, tenant: str, req):
        """Register a standing tail query (docs/search-live-tail.md).
        None = hot tier disabled, or the tenant's subscription cap is
        reached — the HTTP layer maps the two to 400/429."""
        from tempo_tpu.search.live_tier import LIVE_TIER

        if not LIVE_TIER.enabled:
            return None
        return LIVE_TIER.subscribe(tenant, req)

    def tail_unsubscribe(self, sub) -> None:
        from tempo_tpu.search.live_tier import LIVE_TIER

        if LIVE_TIER.enabled:
            LIVE_TIER.unsubscribe(sub)

    # ---- maintenance ticks ----

    def flush_tick(self, force: bool = False) -> list:
        completed = []
        for ing in self.ingesters.values():
            completed.extend(ing.sweep(force=force))
        return completed

    def poll_tick(self) -> None:
        self.reader_db.poll()

    def compaction_tick(self) -> None:
        for tenant in self.reader_db.blocklist.tenants():
            self.reader_db.compact_tenant_once(tenant)
            self.reader_db.retain_tenant(tenant)

    def heartbeat_tick(self) -> None:
        for iid in self.ingesters:
            self.ring.heartbeat(iid)
        self.ring.forget_unhealthy()

    # ---- lifecycle ----

    def run_maintenance(self) -> None:
        def loop(tick_s, fn, immediate=False):
            def body():
                if immediate:  # restart must not serve an empty
                    try:       # blocklist for a full poll interval
                        fn()
                    except Exception:  # noqa: BLE001 — keep loops alive,
                        # but a backend broken at boot must not be silent
                        # (microservices.py logs the same failure)
                        log.exception("startup maintenance tick")
                while not self._stop.wait(tick_s):
                    try:
                        fn()
                    except Exception:  # noqa: BLE001 — keep loops alive
                        pass
            t = threading.Thread(target=body, daemon=True)
            t.start()
            self._threads.append(t)

        loop(self.cfg.flush_tick_s, self.flush_tick)
        loop(self.cfg.poll_tick_s, self.poll_tick, immediate=True)
        loop(self.cfg.compaction_tick_s, self.compaction_tick)
        loop(5.0, self.heartbeat_tick)
        if self.remote_write is not None:
            self.remote_write.start()
        if self.canary is not None:
            self.canary.start()
        self.start_receivers()

    def start_receivers(self) -> None:
        """Pull-based ingest receivers (kafka / pubsub-lite)."""
        if self._receivers:
            return
        kcfg = self.cfg.receivers.get("kafka")
        if kcfg:
            from tempo_tpu.api.kafka import KafkaReceiver, KafkaReceiverConfig

            rx = KafkaReceiver(KafkaReceiverConfig(**kcfg), self.push)
            rx.start()
            self._receivers.append(rx)
        pcfg = self.cfg.receivers.get("pubsub_lite")
        if pcfg:
            from tempo_tpu.api.kafka import pubsub_lite_receiver

            rx = pubsub_lite_receiver(pcfg, self.push)
            rx.start()
            self._receivers.append(rx)

    def shutdown(self) -> None:
        """Graceful: flush everything, stop loops (reference /shutdown)."""
        self._stop.set()
        if self.canary is not None:
            self.canary.stop()
        for rx in self._receivers:
            rx.stop()
        self._receivers.clear()
        if self.tracer is not None:
            from tempo_tpu.observability import tracing
            self.tracer.shutdown()
            if tracing.get_tracer() is self.tracer:
                tracing.set_tracer(None)
        flush_left = 0
        for ing in self.ingesters.values():
            try:
                ing.flush_all()
            except FlushIncompleteError as e:
                # keep draining the rest of the process — but the WAL on
                # disk still holds data; a scale-down must not remove it
                log.error("shutdown flush incomplete: %s", e)
                flush_left += e.left_behind
        if self.remote_write is not None:
            self.remote_write.stop(final_ship=True)
        self.poll_tick()
        if flush_left:
            # re-raised AFTER the full drain so an orchestrator driving
            # shutdown() programmatically cannot mistake a partial flush
            # for success and delete the node's WAL volume
            raise FlushIncompleteError(left_behind=flush_left, completed=[])

    def ready(self) -> bool:
        return self.ring.healthy_count() >= self.cfg.replication_factor
