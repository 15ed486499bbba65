"""The server binary: ``python -m tempo_tpu.cli.main -config.file=...``.

Role-equivalent to the reference's cmd/tempo main (config load, logger,
module startup, signal-driven graceful shutdown) with `-target` module
selection (cmd/tempo/app/modules.go:35-50):

  -target=all            single process, whole pipeline (default)
  -target=distributor    OTLP receivers → ring writes over gRPC
  -target=ingester       Pusher/IngesterQuerier gRPC + WAL/flush loops
  -target=querier        Querier gRPC job execution
  -target=query-frontend external HTTP API, job sharding over queriers
  -target=compactor      ownership-gated compaction + retention

Microservice targets discover each other via gossip membership
(`memberlist:` config section — bind/join addresses).
"""

from __future__ import annotations

import argparse
import os
import signal
import threading
import uuid

from tempo_tpu.api import HTTPApi, make_grpc_server, serve_http
from tempo_tpu.modules import App
from tempo_tpu.observability import get_logger
from .config import load_config


def main(argv=None) -> int:
    from tempo_tpu.modules.microservices import TARGETS, ModuleProcess

    p = argparse.ArgumentParser("tempo-tpu")
    p.add_argument("-config.file", dest="config_file", default=None)
    p.add_argument("-target", dest="target", default="all", choices=TARGETS)
    p.add_argument("-http-port", type=int, default=None)
    p.add_argument("-grpc-port", type=int, default=None)
    p.add_argument("-instance-id", dest="instance_id", default=None)
    args = p.parse_args(argv)

    log = get_logger()
    cfg, runtime = load_config(args.config_file)
    for w in runtime["warnings"]:
        log.warning("config: %s", w)

    http_port = args.http_port or runtime["http_port"]
    grpc_port = args.grpc_port or runtime["grpc_port"]

    dist = runtime.get("distributed") or {}
    if dist.get("coordinator") or "TEMPO_COORDINATOR" in os.environ:
        # must run before anything touches jax devices: the scan mesh
        # then spans every host's chips (SURVEY §2.6 TPU note)
        from tempo_tpu.parallel.multihost import init_distributed

        if init_distributed(
            coordinator=dist.get("coordinator"),
            num_processes=dist.get("num_processes"),
            process_id=dist.get("process_id"),
            cpu_devices_per_host=dist.get("cpu_devices_per_host"),
        ):
            log.info("joined distributed runtime")
        else:
            log.info("no coordinator configured; running single-host")

    _log_runtime(log, claims_device=args.target in ("all", "querier"))

    stop = threading.Event()

    def on_signal(signum, frame):
        log.info("signal %s: draining", signum)
        stop.set()

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)

    if args.target == "all":
        app = App(cfg)
        app.run_maintenance()
        api = HTTPApi(app, multitenancy=runtime["multitenancy"],
                      debug_endpoints=runtime["debug_endpoints"])
        http_server = serve_http(api, port=http_port)
        threading.Thread(target=http_server.serve_forever, daemon=True).start()
        grpc_server = make_grpc_server(app, f"0.0.0.0:{grpc_port}")
        grpc_server.start()
        jaeger_agent = None
        if runtime.get("jaeger_agent_port"):
            from tempo_tpu.api.jaeger import JaegerAgentUDP
            jaeger_agent = JaegerAgentUDP(app.push,
                                          port=runtime["jaeger_agent_port"])
        log.info("tempo-tpu up: http=:%d grpc=:%d ingesters=%d rf=%d",
                 http_port, grpc_port, cfg.n_ingesters,
                 cfg.replication_factor)
        stop.wait()
        grpc_server.stop(grace=5)
        http_server.shutdown()
        if jaeger_agent is not None:
            jaeger_agent.close()
        try:
            app.shutdown()  # flush everything (reference /shutdown drain)
        except Exception as e:  # noqa: BLE001 — flush incomplete
            log.error("shutdown finished with unflushed WAL data: %s — "
                      "do NOT delete this node's WAL directory", e)
            return 1
        log.info("shutdown complete")
        return 0

    # microservice target
    instance_id = (args.instance_id or runtime["instance_id"]
                   or f"{args.target}-{uuid.uuid4().hex[:6]}")
    proc = ModuleProcess(
        cfg, args.target, instance_id=instance_id,
        grpc_port=grpc_port if args.target in
        ("ingester", "querier", "distributor", "query-frontend",
         "metrics-generator") else 0,
        http_port=http_port,
        memberlist_cfg=runtime["memberlist"],
    )
    api = HTTPApi(proc, multitenancy=runtime["multitenancy"],
                  debug_endpoints=runtime["debug_endpoints"])
    http_server = serve_http(api, port=http_port)
    threading.Thread(target=http_server.serve_forever, daemon=True).start()
    jaeger_agent = None
    if runtime.get("jaeger_agent_port"):
        if args.target == "distributor":
            from tempo_tpu.api.jaeger import JaegerAgentUDP
            jaeger_agent = JaegerAgentUDP(proc.push,
                                          port=runtime["jaeger_agent_port"])
        else:
            log.warning("jaeger_agent_port is only served by the "
                        "distributor target (ignored for %s)", args.target)
    log.info("tempo-tpu %s up: id=%s http=:%d grpc=%s gossip=%s",
             args.target, instance_id, http_port, proc.grpc_addr or "-",
             proc.ml.gossip_addr)
    stop.wait()
    http_server.shutdown()
    if jaeger_agent is not None:
        jaeger_agent.close()
    try:
        proc.shutdown()
    except Exception as e:  # noqa: BLE001 — flush incomplete
        log.error("shutdown finished with unflushed WAL data: %s — "
                  "do NOT delete this node's WAL directory", e)
        return 1
    log.info("shutdown complete")
    return 0


def _log_runtime(log, claims_device: bool) -> None:
    """The one startup line that says what this process runs on:
    platform, device kind and count, native runtime, compile-cache
    directory. Targets that scan (all, querier) initialize the backend
    here — they own the chip for their lifetime, and a chip that cannot
    be claimed must stop the process now, not degrade the first query.
    Write-only targets never touch a device."""
    from tempo_tpu.ops import native
    from tempo_tpu.utils.jaxenv import compile_cache_dir, enable_compile_cache

    if claims_device:
        import jax

        cache = enable_compile_cache()
        devs = jax.devices()
        platform, kind, count = (devs[0].platform, devs[0].device_kind,
                                 len(devs))
    else:
        cache = compile_cache_dir()
        platform, kind, count = "unclaimed", "-", 0
    log.info("runtime: platform=%s device_kind=%s device_count=%d "
             "native=%s compile_cache=%s", platform, kind, count,
             "loaded" if native.available() else "absent",
             cache or "disabled")


if __name__ == "__main__":
    raise SystemExit(main())
