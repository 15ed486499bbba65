"""Cache, queues, metrics-generator, CLI tooling, vulture."""

import io
import json
import sys
import threading
import time

import pytest

from tempo_tpu import tempopb
from tempo_tpu.backend import MockBackend, LocalBackend
from tempo_tpu.backend.cache import CachedBackend, LRUCache
from tempo_tpu.modules import App, AppConfig
from tempo_tpu.modules.generator import (
    MetricsGenerator,
    ServiceGraphProcessor,
    SpanMetricsProcessor,
)
from tempo_tpu.modules.queue import ExclusiveQueue, RequestQueue, TooManyRequests
from tempo_tpu.observability.metrics import Registry
from tempo_tpu.cli.vulture import Vulture
from tempo_tpu.utils.ids import random_trace_id
from tempo_tpu.utils.test_data import make_trace


# ---- cache ----

def test_cached_backend_read_through():
    inner = MockBackend()
    cb = CachedBackend(inner, LRUCache(max_bytes=1 << 20))
    cb.write("t", "b", "index", b"idx")
    cb.write("t", "b", "data", b"data")
    inner.read_count = 0
    for _ in range(5):
        assert cb.read("t", "b", "index") == b"idx"
    assert inner.read_count == 0  # warmed by write-through
    for _ in range(5):
        cb.read("t", "b", "data")
    assert inner.read_count == 5  # data is never cached


def test_lru_eviction():
    c = LRUCache(max_bytes=100)
    c.store("a", b"x" * 60)
    c.store("b", b"y" * 60)  # evicts a
    assert c.fetch("a") is None
    assert c.fetch("b") is not None


# ---- queues ----

def test_request_queue_tenant_fairness():
    q = RequestQueue()
    for i in range(3):
        q.enqueue("noisy", f"n{i}")
    q.enqueue("quiet", "q0")
    served = [q.get(timeout=0.1)[0] for _ in range(3)]
    # quiet tenant is served within the first rounds, not starved
    assert "quiet" in served


def test_request_queue_max_outstanding():
    # the cap counts top-level request brackets, not queued sub-requests
    # (reference v1/frontend.go:46-48)
    q = RequestQueue(max_outstanding_per_tenant=2)
    q.begin_request("t")
    q.begin_request("t")
    with pytest.raises(TooManyRequests):
        q.begin_request("t")
    q.end_request("t")
    q.begin_request("t")  # slot released -> admitted again
    assert q.outstanding("t") == 2


def test_exclusive_queue_dedupes_inflight():
    q = ExclusiveQueue()
    assert q.enqueue("block-1", 1.0, "op")
    assert not q.enqueue("block-1", 0.5, "dup")  # queued → refused
    key, item = q.dequeue()
    assert not q.enqueue("block-1", 0.5, "dup")  # in-flight → refused
    q.done(key)
    assert q.enqueue("block-1", 0.5, "retry")    # released → accepted


# ---- metrics generator ----

def _client_server_pair(tid, client_svc="web", server_svc="db", error=False):
    client = tempopb.ResourceSpans()
    kv = client.resource.attributes.add()
    kv.key = "service.name"
    kv.value.string_value = client_svc
    cs = client.scope_spans.add().spans.add()
    cs.trace_id = tid
    cs.span_id = b"\x01" * 8
    cs.kind = tempopb.Span.SPAN_KIND_CLIENT
    cs.start_time_unix_nano = 10**9
    cs.end_time_unix_nano = int(1.5e9)

    server = tempopb.ResourceSpans()
    kv = server.resource.attributes.add()
    kv.key = "service.name"
    kv.value.string_value = server_svc
    ss = server.scope_spans.add().spans.add()
    ss.trace_id = tid
    ss.span_id = b"\x02" * 8
    ss.parent_span_id = cs.span_id
    ss.kind = tempopb.Span.SPAN_KIND_SERVER
    if error:
        ss.status.code = tempopb.Status.STATUS_CODE_ERROR
    return client, server


def test_spanmetrics_processor():
    reg = Registry()
    p = SpanMetricsProcessor(reg)
    tid = random_trace_id()
    p.consume(make_trace(tid, seed=1).batches[0])
    out = reg.expose()
    assert "traces_spanmetrics_calls_total" in out
    assert "traces_spanmetrics_latency_bucket" in out


def test_spanmetrics_non_string_service_label():
    """ADVICE r5: a non-string service.name (int/bool/double) must label
    the series with the stringified AnyValue — matching search-data
    extraction and the native summary feed — not the empty string
    .string_value yields."""
    reg = Registry()
    p = SpanMetricsProcessor(reg)
    for field, val, want in (("int_value", 123, "123"),
                             ("bool_value", True, "true"),
                             ("double_value", 2.5, "2.5")):
        b = tempopb.ResourceSpans()
        kv = b.resource.attributes.add()
        kv.key = "service.name"
        setattr(kv.value, field, val)
        sp = b.scope_spans.add().spans.add()
        sp.trace_id = random_trace_id()
        sp.name = "op"
        sp.start_time_unix_nano = 1
        sp.end_time_unix_nano = 2
        p.consume(b)
        assert f'service="{want}"' in reg.expose()


def test_service_graph_non_string_service_label():
    reg = Registry()
    p = ServiceGraphProcessor(reg)
    client, server = _client_server_pair(random_trace_id())
    for half in (client, server):
        for kv in half.resource.attributes:
            if kv.key == "service.name":
                kv.value.int_value = 7  # clears string_value (oneof)
    p.consume(client)
    p.consume(server)
    assert p.requests.value(client="7", server="7") == 1


def test_service_graph_pairs_edges():
    reg = Registry()
    p = ServiceGraphProcessor(reg)
    client, server = _client_server_pair(random_trace_id())
    p.consume(client)
    p.consume(server)
    assert p.requests.value(client="web", server="db") == 1
    assert p.failed.value(client="web", server="db") == 0

    c2, s2 = _client_server_pair(random_trace_id(), error=True)
    p.consume(s2)  # server first — order must not matter
    p.consume(c2)
    assert p.requests.value(client="web", server="db") == 2
    assert p.failed.value(client="web", server="db") == 1


def test_generator_end_to_end_via_app(tmp_path):
    app = App(AppConfig(wal_dir=str(tmp_path / "wal")))
    tid = random_trace_id()
    app.push("t1", list(make_trace(tid, seed=5).batches))
    app.distributor.forward_flush()  # forwarder is async off the hot path
    out = app.generator.collect("t1")
    assert "traces_spanmetrics_calls_total" in out


def test_generator_series_limit():
    gen = MetricsGenerator(max_active_series=1)
    tid = random_trace_id()
    gen.push_spans("t", list(make_trace(tid, seed=1).batches))
    before = gen.dropped_over_limit
    gen.push_spans("t", list(make_trace(random_trace_id(), seed=2).batches))
    assert gen.dropped_over_limit > before


# ---- CLI ----

def test_cli_block_tooling(tmp_path, capsys):
    from tempo_tpu.cli import blocks as cli

    # build a block via the app
    app = App(AppConfig(
        backend={"backend": "local", "local": {"path": str(tmp_path / "be")}},
        wal_dir=str(tmp_path / "wal"),
    ))
    tid = random_trace_id()
    app.push("t1", list(make_trace(tid, seed=9).batches))
    app.flush_tick(force=True)

    assert cli.main(["--backend-path", str(tmp_path / "be"),
                     "list-blocks", "t1"]) == 0
    rows = json.loads(capsys.readouterr().out)
    assert len(rows) == 1 and rows[0]["objects"] == 1
    bid = rows[0]["id"]

    assert cli.main(["--backend-path", str(tmp_path / "be"),
                     "view-block", "t1", bid]) == 0
    view = json.loads(capsys.readouterr().out)
    assert view["total_objects"] == 1 and view["pages"]

    assert cli.main(["--backend-path", str(tmp_path / "be"),
                     "find", "t1", bid, tid.hex()]) == 0
    assert "batches" in capsys.readouterr().out

    # destroy + regenerate bloom, then find still works
    assert cli.main(["--backend-path", str(tmp_path / "be"),
                     "gen-bloom", "t1", bid]) == 0
    capsys.readouterr()
    assert cli.main(["--backend-path", str(tmp_path / "be"),
                     "find", "t1", bid, tid.hex()]) == 0
    capsys.readouterr()

    assert cli.main(["--backend-path", str(tmp_path / "be"),
                     "search", "t1", "--tags", "component=db"]) == 0
    capsys.readouterr()

    # duration/window filters parse and apply (a 1h floor excludes all)
    assert cli.main(["--backend-path", str(tmp_path / "be"),
                     "search", "t1", "--min-duration", "3600s"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert not out.get("traces")


# ---- vulture ----

def test_vulture_consistency_cycle(tmp_path):
    app = App(AppConfig(wal_dir=str(tmp_path / "wal")))
    v = Vulture(app)
    stats = v.run_cycle(n=5)
    assert stats.written == 5
    assert stats.found == 5 and stats.missing == 0 and stats.mismatched == 0
    assert stats.search_found == 5 and stats.search_missing == 0

    # and again after a flush (block path)
    app.flush_tick(force=True)
    app.poll_tick()
    v.read_pass()
    assert v.stats.missing == 0


# ---- shuffle shard / quorum / hedging / serverless / receivers ----

def test_shuffle_shard_deterministic_and_isolated():
    from tempo_tpu.modules import Ring

    ring = Ring(replication_factor=2)
    for i in range(10):
        ring.register(f"i{i}")
    a1 = ring.shuffle_shard("tenant-a", 3)
    a2 = ring.shuffle_shard("tenant-a", 3)
    b = ring.shuffle_shard("tenant-b", 3)
    assert a1.instance_ids() == a2.instance_ids()
    assert len(a1.instance_ids()) == 3
    assert a1.instance_ids() != b.instance_ids()  # overwhelmingly likely
    # placement inside the sub-ring only uses its instances
    got = a1.get(12345)
    assert set(got) <= set(a1.instance_ids())


def test_write_quorum_one_mode(tmp_path):
    """RF=2 eventual-consistency: one replica down, quorum 'one' accepts
    the write while 'majority' (2 of 2) rejects it."""
    from tempo_tpu.modules import App, AppConfig
    from tempo_tpu.modules.distributor import Distributor, IngestError

    app = App(AppConfig(wal_dir=str(tmp_path / "wal"), n_ingesters=2,
                        replication_factor=2))

    class Broken:
        def push_bytes(self, *a):
            raise OSError("down")

    pushers = dict(app.ingesters)
    pushers[next(iter(pushers))] = Broken()

    tid = random_trace_id()
    tr = make_trace(tid, seed=1)
    strict = Distributor(app.ring, pushers, app.overrides)
    with pytest.raises(IngestError):
        strict.push_batches("t1", list(tr.batches))
    eventual = Distributor(app.ring, pushers, app.overrides,
                           write_quorum="one")
    eventual.push_batches("t1", list(tr.batches))  # succeeds


def test_hedged_call_returns_fast_result():
    from tempo_tpu.db.hedge import hedged_call

    calls = []

    def slow_then_fast():
        calls.append(1)
        if len(calls) == 1:
            time.sleep(2.0)
            return "slow"
        return "fast"

    out = hedged_call(slow_then_fast, hedge_after_s=0.05, max_hedges=2)
    assert out == "fast"


def test_hedged_backend_passthrough():
    from tempo_tpu.db.hedge import HedgedBackend

    inner = MockBackend()
    hb = HedgedBackend(inner, hedge_after_s=5)
    hb.write("t", "b", "data", b"abc")  # __getattr__ passthrough
    assert hb.read("t", "b", "data") == b"abc"
    assert hb.read_range("t", "b", "data", 1, 1) == b"b"


def test_serverless_worker_and_external_querier(tmp_path):
    import threading

    from tempo_tpu.modules import App, AppConfig
    from tempo_tpu.modules.querier import Querier
    from tempo_tpu.serverless import SearchWorker, serve_worker

    app = App(AppConfig(
        backend={"backend": "local", "local": {"path": str(tmp_path / "be")}},
        wal_dir=str(tmp_path / "wal"),
    ))
    traces = {}
    for i in range(10):
        tid = random_trace_id()
        app.push("t1", list(make_trace(tid, seed=i).batches))
        traces[tid] = 1
    app.flush_tick(force=True)
    app.poll_tick()
    meta = app.reader_db.blocklist.metas("t1")[0]

    worker = SearchWorker(app.backend, wal_dir=str(tmp_path / "worker-wal"))
    server = serve_worker(worker, host="127.0.0.1", port=0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        req = tempopb.SearchBlockRequest()
        req.tenant_id = "t1"
        req.block_id = meta.block_id
        req.search_req.limit = 100

        # querier with prefer_self=0 → every job goes external
        q = Querier(app.reader_db, app.ring, app.ingesters,
                    external_endpoints=[f"http://127.0.0.1:{port}"],
                    prefer_self=0, external_hedge_after_s=5.0)
        resp = q.search_block(req)
        assert len(resp.traces) == 10

        # malformed body → 400 (a hedging caller must not retry it)
        import urllib.error
        import urllib.request

        bad = urllib.request.Request(
            f"http://127.0.0.1:{port}/search-block",
            data=b"\xff\xfenot-a-proto-message-at-all" * 3,
            headers={"Content-Type": "application/protobuf"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad, timeout=5)
        assert ei.value.code == 400
    finally:
        server.shutdown()


def test_zipkin_receiver(tmp_path):
    from tempo_tpu.api import HTTPApi
    from tempo_tpu.modules import App, AppConfig

    app = App(AppConfig(wal_dir=str(tmp_path / "wal")))
    api = HTTPApi(app)
    tid = "0102030405060708090a0b0c0d0e0f10"
    spans = [
        {"traceId": tid, "id": "1112131415161718", "name": "get /",
         "kind": "SERVER", "timestamp": 1_600_000_000_000_000,
         "duration": 250_000,
         "localEndpoint": {"serviceName": "shop"},
         "tags": {"http.method": "GET"}},
        {"traceId": tid, "id": "2122232425262728",
         "parentId": "1112131415161718", "name": "q",
         "kind": "CLIENT", "timestamp": 1_600_000_000_050_000,
         "duration": 100_000,
         "localEndpoint": {"serviceName": "db"}},
    ]
    code, body = api.handle("POST", "/api/v2/spans", {},
                            {"X-Scope-OrgID": "t1"},
                            json.dumps(spans).encode())
    assert code == 200 and body["accepted_batches"] == 2

    resp = app.find_trace("t1", bytes.fromhex(tid))
    assert len(resp.trace.batches) == 2
    names = {s.name for b in resp.trace.batches
             for ss in b.scope_spans for s in ss.spans}
    assert names == {"get /", "q"}


def test_otlp_http_receiver(tmp_path):
    from tempo_tpu.api import HTTPApi
    from tempo_tpu.modules import App, AppConfig

    app = App(AppConfig(wal_dir=str(tmp_path / "wal")))
    api = HTTPApi(app)
    tid = random_trace_id()
    tr = make_trace(tid, seed=3)
    code, body = api.handle("POST", "/v1/traces", {},
                            {"X-Scope-OrgID": "t1"}, tr.SerializeToString())
    assert code == 200
    resp = app.find_trace("t1", tid)
    assert len(resp.trace.batches) == len(tr.batches)


def test_request_queue_sub_request_memory_bound():
    """Complementary to the request cap: queued sub-requests are bounded
    per tenant so frontend memory cannot grow without limit."""
    q = RequestQueue(max_outstanding_per_tenant=10, max_queued_per_tenant=3)
    for i in range(3):
        q.enqueue("t", i)
    with pytest.raises(TooManyRequests):
        q.enqueue("t", 3)
