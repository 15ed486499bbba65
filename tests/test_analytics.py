"""Device-side aggregate analytics (ISSUE 19): the differential
contract. Ingest — random native summary corpora through the batched
device reduction must leave the generator registries BYTE-identical to
the per-span Python walk (exposition bytes, LRU recency order, pairing
store), packed composite keys on and off, breaker-forced host routes
included. Query — ``?agg=red`` answers byte-identically through every
engine path (batched / coalesced / mesh / both host routes) and equals
a plain-python reference aggregator; the default-off gate is a true
noop (WAL and /metrics byte-identity, 400 on ?agg=)."""

from __future__ import annotations

import bisect
import json
import random
import struct
import threading

import numpy as np
import pytest

from tempo_tpu import robustness, tempopb
from tempo_tpu.backend.local import LocalBackend
from tempo_tpu.db import TempoDB, TempoDBConfig
from tempo_tpu.modules.generator import (
    LATENCY_BUCKETS_S,
    MetricsGenerator,
    ServiceGraphProcessor,
    SpanMetricsProcessor,
)
from tempo_tpu.observability import metrics as obs
from tempo_tpu.search.analytics import (
    AGG_QUERY_TAG,
    ANALYTICS,
    MS_BUCKETS,
    _dur_thresholds,
    _dur_thresholds_full,
    agg_requested,
    agg_response,
    attach_agg,
    merge_agg,
)
from tempo_tpu.search.batcher import host_scan
from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
from tempo_tpu.search.data import SearchData, encode_search_data
from tempo_tpu.search.multiblock import (
    MultiBlockEngine,
    compile_multi,
    stack_queries,
)

E_GEO = PageGeometry(entries_per_page=64, kv_per_entry=8)

_SVCS = ["api", "db", "auth", "cache", "web", "api"]  # dup: canon remap
_OPS = ["op0", "op1", "op2"]


@pytest.fixture(autouse=True)
def _analytics_gate():
    """Leave the process-wide gate and breaker as the test found them."""
    prev_enabled, prev_min = ANALYTICS.enabled, ANALYTICS.min_rows
    prev_brk = robustness.BREAKER.enabled
    prev_thr = robustness.BREAKER.threshold
    yield
    ANALYTICS.configure(enabled=prev_enabled, min_rows=prev_min)
    robustness.BREAKER.enabled = prev_brk
    robustness.BREAKER.threshold = prev_thr
    robustness.BREAKER.reset()


# ---------------------------------------------------------------------------
# native summary blob construction (the MetricsGenerator._ROW ABI)

_ROW = struct.Struct("<6IQQ8s8s")


def _blob(strs: list[str], rows: list[tuple]) -> bytes:
    out = [struct.pack("<I", len(strs))]
    for s in strs:
        b = s.encode()
        out.append(struct.pack("<H", len(b)))
        out.append(b)
    out.append(struct.pack("<I", len(rows)))
    for r in rows:
        out.append(_ROW.pack(*r))
    return b"".join(out)


def _rand_push(rng: random.Random, n_traces: int = 24,
               big_enums: bool = False):
    """One push: a string table (with deliberate duplicates), trace ids
    (with deliberate duplicate bytes), and summary rows mixing paired
    client/server edges, half pairs, and plain spans. ``big_enums``
    drives kind/status into ranges that overflow the packed int64
    composite key, forcing the 2-D unique fallback."""
    strs = _SVCS + _OPS + [rng.choice(_SVCS)]
    tids = [rng.getrandbits(64).to_bytes(8, "big").rjust(16, b"\x00")
            for _ in range(n_traces)]
    if n_traces >= 2 and rng.random() < 0.5:
        tids[1] = tids[0]          # duplicate trace-id bytes
    rows = []
    sid_n = 1
    # bucket-edge-exact durations: T and T-1 for random thresholds
    edge_durs = [t + d for t in _dur_thresholds_full(LATENCY_BUCKETS_S)
                 for d in (-1, 0)]
    for ti in range(n_traces):
        for _ in range(rng.randint(1, 5)):
            kind = rng.randint(0, 5)
            status = rng.randint(0, 2)
            if big_enums:
                kind = rng.choice([rng.randint(0, 5),
                                   rng.randint(1 << 30, (1 << 32) - 1)])
                status = rng.randint(1 << 30, (1 << 32) - 1)
            start = rng.randrange(1 << 40)
            dur = (rng.choice(edge_durs) if rng.random() < 0.3
                   else rng.randrange(20_000_000_000))
            sid = sid_n.to_bytes(8, "little")
            sid_n += 1
            if kind in (2, 3) and rng.random() < 0.7:
                # paired edge: client sid == server pid, same trace
                pid = sid_n.to_bytes(8, "little")
                sid_n += 1
                a = (ti, rng.randrange(len(_SVCS)), len(_SVCS)
                     + rng.randrange(len(_OPS)), 3, status, 0,
                     start, start + dur, sid, b"\x00" * 8)
                b = (ti, rng.randrange(len(_SVCS)), len(_SVCS)
                     + rng.randrange(len(_OPS)), 2, rng.randint(0, 2),
                     0, start, start + rng.randrange(dur + 1), pid, sid)
                pair = [a, b]
                rng.shuffle(pair)
                rows.extend(pair)
            else:
                rows.append((ti, rng.randrange(len(strs)),
                             len(_SVCS) + rng.randrange(len(_OPS)),
                             kind, status, 0, start, start + dur, sid,
                             rng.getrandbits(64).to_bytes(8, "little")))
    rng.shuffle(rows)
    return strs, rows, tids


def _feed(pushes, enabled: bool, min_rows: int = 1) -> MetricsGenerator:
    ANALYTICS.configure(enabled=enabled, min_rows=min_rows)
    gen = MetricsGenerator()
    for strs, rows, tids in pushes:
        gen.push_summary_blob("t", _blob(strs, rows), tids)
    return gen


def _snap(gen: MetricsGenerator):
    """(exposition bytes, spanmetrics LRU order, pairing-store state) —
    store timestamps dropped: wall-clock, legitimately different."""
    _reg, procs = gen._instance("t")
    spm = next(p for p in procs if isinstance(p, SpanMetricsProcessor))
    sgp = next(p for p in procs if isinstance(p, ServiceGraphProcessor))
    store = {k: v[:3] for k, v in sgp._store.items()}
    return gen.collect("t"), list(spm._series.keys()), store


# ---------------------------------------------------------------------------
# ingest parity


def test_two_limb_thresholds_are_exact():
    """T = min{n : n/1e9 > edge}: n >= T iff n/1e9 > edge, and the limb
    split round-trips."""
    full = _dur_thresholds_full(LATENCY_BUCKETS_S)
    limbs = _dur_thresholds(LATENCY_BUCKETS_S)
    for edge, T, (hi, lo) in zip(LATENCY_BUCKETS_S, full, limbs):
        assert (hi << 31) | lo == T
        assert T / 1e9 > edge
        assert (T - 1) / 1e9 <= edge
        # the device bin (count of thresholds <=) equals the walk's
        # bisect over the float edges at the exact boundary
        for dur in (T - 1, T, T + 1):
            dev_bin = sum(dur >= t for t in full)
            assert dev_bin == bisect.bisect_left(
                LATENCY_BUCKETS_S, dur / 1e9)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_ingest_differential_parity(seed):
    """The core contract: walk-fed and device-fed registries are
    byte-identical — exposition, LRU recency order, pairing store."""
    rng = random.Random(1000 + seed)
    pushes = [_rand_push(rng) for _ in range(4)]
    walk = _snap(_feed(pushes, enabled=False))
    dev = _snap(_feed(pushes, enabled=True))
    assert dev[0] == walk[0]
    assert dev[1] == walk[1]
    assert dev[2] == walk[2]


def test_ingest_parity_packed_key_overflow():
    """kind/status near 2^32 overflow the packed int64 composite key —
    the 2-D unique fallback must stay byte-identical too."""
    rng = random.Random(77)
    pushes = [_rand_push(rng, big_enums=True) for _ in range(3)]
    walk = _snap(_feed(pushes, enabled=False))
    dev = _snap(_feed(pushes, enabled=True))
    assert dev == walk


def test_ingest_parity_on_breaker_host_route():
    """Breaker open: the numpy bincount fallback answers, still
    byte-identical, and books route=host."""
    rng = random.Random(88)
    pushes = [_rand_push(rng) for _ in range(2)]
    walk = _snap(_feed(pushes, enabled=False))
    robustness.BREAKER.reset()
    robustness.BREAKER.enabled = True
    robustness.BREAKER.threshold = 1
    robustness.BREAKER.record_fault("timeout", mode="batched")
    assert robustness.BREAKER.state == "open"
    host0 = obs.search_analytics_dispatches.value(route="host")
    dev = _snap(_feed(pushes, enabled=True))
    assert dev == walk
    assert obs.search_analytics_dispatches.value(route="host") > host0
    robustness.BREAKER.reset()


def test_gate_off_and_small_blob_fall_back_to_walk():
    rng = random.Random(5)
    strs, rows, tids = _rand_push(rng, n_traces=3)
    blob = _blob(strs, rows)
    gen = MetricsGenerator()
    _reg, procs = gen._instance("t")
    # gate off: one attribute read, no consumption, no dispatch booked
    ANALYTICS.configure(enabled=False)
    d0 = (obs.search_analytics_dispatches.value(route="device")
          + obs.search_analytics_dispatches.value(route="host"))
    off = len(blob) - len(rows) * _ROW.size - 4
    assert ANALYTICS.consume_blob(procs, strs, blob, off + 4,
                                  len(rows), tids) is False
    # min_rows: tiny blobs stay on the walk
    ANALYTICS.configure(enabled=True, min_rows=len(rows) + 1)
    assert ANALYTICS.consume_blob(procs, strs, blob, off + 4,
                                  len(rows), tids) is False
    # unknown processor type: hands back to the walk
    ANALYTICS.configure(enabled=True, min_rows=1)
    assert ANALYTICS.consume_blob(procs + [object()], strs, blob,
                                  off + 4, len(rows), tids) is False
    assert (obs.search_analytics_dispatches.value(route="device")
            + obs.search_analytics_dispatches.value(route="host")) == d0
    assert gen.collect("t") == _feed([], enabled=False).collect("t")


def test_gate_off_wal_bytes_identical(tmp_path):
    """The gate is a true noop on the write path: identical pushes with
    the gate on and off leave byte-identical WAL files."""
    from tempo_tpu.modules import App, AppConfig
    from tempo_tpu.utils.test_data import make_trace

    wals = {}
    for on in (False, True):
        ANALYTICS.configure(enabled=on)
        wal = tmp_path / f"wal_{on}"
        app = App(AppConfig(
            wal_dir=str(wal),
            db=TempoDBConfig(auto_mesh=False,
                             search_analytics_enabled=on)))
        for i in range(6):
            tid = bytes([i + 1]) * 16
            app.push("t1", list(make_trace(tid, seed=i).batches))
        # block dirs carry random UUIDs — normalize the name, keep the
        # (tenant, version, codec) suffix and the bytes
        ents = []
        for p in (q for q in wal.rglob("*") if q.is_file()):
            name = "+".join(p.name.split("+")[1:]) or p.name
            ents.append((str(p.parent.relative_to(wal)), name,
                         p.read_bytes()))
        wals[on] = sorted(ents)
    assert wals[True] == wals[False]


# ---------------------------------------------------------------------------
# satellite behaviors: LRU eviction, bounded expiry sweeps


def test_spanmetrics_series_cache_is_lru_not_fifo():
    from tempo_tpu.observability.metrics import Registry

    spm = SpanMetricsProcessor(Registry())
    k = [("s%d" % i, "op", 0, 0) for i in range(65_537)]
    for key in k[:-1]:            # fill exactly to the cap
        spm._series_touch(key)
    assert len(spm._series) == 65_536
    spm._series_touch(k[0])       # re-touch the oldest-CREATED series
    spm._series_touch(k[-1])      # one past the cap → one eviction
    assert len(spm._series) == 65_536
    # FIFO (insertion order) would evict k[0]; LRU evicts the coldest
    assert k[0] in spm._series
    assert k[1] not in spm._series
    assert list(spm._series)[-2:] == [k[0], k[-1]]


def test_servicegraph_expiry_is_bounded_and_counted():
    from tempo_tpu.observability.metrics import Registry

    sgp = ServiceGraphProcessor(Registry(), wait_s=0.0)
    sgp.max_expire_per_sweep = 4
    now = 100.0
    for i in range(10):
        sgp._pair((b"t", i.to_bytes(8, "little")), "client", "api",
                  (0, 0, 1), now)
    assert len(sgp._store) == 10
    sgp._expire(now + 1.0)        # bounded: at most 4 per sweep
    assert len(sgp._store) == 6
    assert sgp.expired == 4
    assert sgp.expired_total.value() == 4
    sgp._expire(now + 1.0)
    assert len(sgp._store) == 2
    assert sgp.expired_total.value() == 8
    sgp._expire(now + 1.0)
    assert len(sgp._store) == 0
    assert sgp.expired_total.value() == 10


def test_pairing_capacity_sweeps_inline_before_dropping():
    """At max_items the insert sweeps expired squatters inline instead
    of dropping the edge."""
    from tempo_tpu.observability.metrics import Registry

    sgp = ServiceGraphProcessor(Registry(), wait_s=1.0, max_items=4)
    for i in range(4):
        sgp._pair((b"t", i.to_bytes(8, "little")), "client", "api",
                  (0, 0, 1), 0.0)
    # all four are expired at t=10; the fifth insert must land
    sgp._pair((b"t", b"\xff" * 8), "client", "api", (0, 0, 1), 10.0)
    assert (b"t", b"\xff" * 8) in sgp._store
    assert sgp.expired_total.value() == 4


# ---------------------------------------------------------------------------
# query-side ?agg=


def _corpus(seed: int, n: int = 150):
    rng = random.Random(seed)
    entries = []
    for i in range(n):
        sd = SearchData(trace_id=i.to_bytes(2, "big").rjust(16, b"\x00"))
        sd.start_s = 1_600_000_000 + i
        sd.end_s = sd.start_s + rng.randint(0, 10)
        # durations hit the integer-ms edges exactly
        sd.dur_ms = rng.choice([rng.randint(1, 20_000)]
                               + [e + d for e in MS_BUCKETS
                                  for d in (0, 1)])
        sd.root_service = rng.choice(_SVCS)
        sd.kvs = {"service.name": {sd.root_service},
                  "env": {"prod" if i % 2 else "dev"}}
        if rng.random() < 0.3:
            sd.kvs["error"] = {"true"}
        entries.append(sd)
    return entries


def _ref_series(entries, pred) -> dict:
    """The plain-python reference aggregator ?agg=red must equal."""
    series = {}
    for sd in entries:
        if not pred(sd):
            continue
        s = series.setdefault(sd.root_service or "", {
            "calls": 0, "errors": 0,
            "hist": [0] * (len(MS_BUCKETS) + 1)})
        s["calls"] += 1
        s["errors"] += int("true" in sd.kvs.get("error", ()))
        s["hist"][bisect.bisect_left(MS_BUCKETS, sd.dur_ms)] += 1
    return series


def _mk_req(tags: dict, limit: int = 4096) -> tempopb.SearchRequest:
    req = tempopb.SearchRequest()
    req.limit = limit
    for k, v in tags.items():
        req.tags[k] = v
    attach_agg(req, "red")
    return req


def _pred(tags):
    def p(sd):
        return all(any(v in x for x in sd.kvs.get(k, ()))
                   for k, v in tags.items())
    return p


def test_agg_grammar_and_merge():
    req = tempopb.SearchRequest()
    attach_agg(req, " RED ")
    assert req.tags[AGG_QUERY_TAG] == "red" and agg_requested(req)
    with pytest.raises(ValueError):
        attach_agg(req, "p99")
    a = agg_response({"api": {"calls": 2, "errors": 1,
                              "hist": [1, 1] + [0] * 13}})
    b = agg_response({"api": {"calls": 3, "errors": 0,
                              "hist": [0, 3] + [0] * 13},
                      "db": {"calls": 1, "errors": 0,
                             "hist": [1] + [0] * 14}})
    m = merge_agg(a, b)
    assert m["series"]["api"] == {"calls": 5, "errors": 1,
                                  "hist": [1, 4] + [0] * 13}
    assert m["series"]["db"]["calls"] == 1
    assert merge_agg(None, a) is a and merge_agg(a, None) is a


@pytest.mark.parametrize("tags", [{"env": "prod"}, {"env": "dev"},
                                  {"service.name": "a"}])
def test_agg_engine_paths_byte_identical(tags):
    """Batched device, host route, mesh, and coalesced dispatches all
    decode to the reference aggregate — integer counts, identical by
    construction."""
    ANALYTICS.configure(enabled=True)
    entries = _corpus(31)
    half = len(entries) // 2
    blocks = [ColumnarPages.build(entries[:half], E_GEO),
              ColumnarPages.build(entries[half:], E_GEO)]
    want = _ref_series(entries, _pred(tags))
    req = _mk_req(tags)

    eng = MultiBlockEngine(top_k=512)
    host = eng.stage_host(blocks)
    batch = eng.place(host)
    mq = compile_multi(blocks, req, cache_on=batch)
    assert mq is not None
    mq.agg_stage = ANALYTICS.stage_for_batch(batch)
    count, _ins, _s, _i, *ext = eng.scan(batch, mq)
    assert ext, "batched dispatch dropped the agg output"
    got_dev = mq.agg_stage.decode(ext[0])
    assert got_dev == want
    assert sum(s["calls"] for s in got_dev.values()) == count

    # breaker-style host route
    mq_h = compile_multi(blocks, req, cache_on=batch, host_only=True)
    mq_h.agg_stage = ANALYTICS.stage_for_batch(host)
    _c, _i2, _s2, _x2, *ext_h = host_scan(host, mq_h, 512)
    assert ext_h and mq_h.agg_stage.decode(ext_h[0]) == want

    # coalesced: three members, same batch-global stage
    mqs = []
    for other in ({"env": "prod"}, tags, {"env": "dev"}):
        m = compile_multi(blocks, _mk_req(other), cache_on=batch)
        m.agg_stage = mq.agg_stage
        mqs.append(m)
    cq = stack_queries(mqs)
    assert cq.agg_stage is mq.agg_stage
    _cs, _i3, _s3, _x3, *ext_c = eng.fetch(
        eng.coalesced_scan_async(batch, cq, 512), cq)
    assert ext_c
    for qi, other in enumerate(({"env": "prod"}, tags, {"env": "dev"})):
        assert mq.agg_stage.decode(ext_c[0][qi]) == \
            _ref_series(entries, _pred(other)), other

    # mesh (8 virtual CPU devices, conftest)
    from tempo_tpu.parallel import make_mesh

    eng_m = MultiBlockEngine(top_k=512, mesh=make_mesh())
    host_m = eng_m.stage_host(blocks)
    batch_m = eng_m.place(host_m)
    mq_m = compile_multi(blocks, req, cache_on=batch_m)
    mq_m.agg_stage = ANALYTICS.stage_for_batch(batch_m)
    _cm, _im, _sm, _xm, *ext_m = eng_m.scan(batch_m, mq_m)
    assert ext_m and mq_m.agg_stage.decode(ext_m[0]) == want


def _mkdb(tmp_path, entries, **cfg_kw) -> TempoDB:
    cfg_kw.setdefault("auto_mesh", False)
    cfg_kw.setdefault("search_analytics_enabled", True)
    be = LocalBackend(str(tmp_path / "blocks"))
    db = TempoDB(be, str(tmp_path / "wal"), TempoDBConfig(**cfg_kw))
    half = len(entries) // 2
    for chunk in (entries[:half], entries[half:]):
        db.write_block_direct(
            "t", [(sd.trace_id, encode_search_data(sd), sd.start_s,
                   sd.end_s) for sd in chunk],
            search_entries=chunk)
    return db


def test_agg_serving_path_and_host_route(tmp_path):
    entries = _corpus(41, n=120)
    db = _mkdb(tmp_path, entries)
    req = _mk_req({"env": "prod"}, limit=1000)
    want = agg_response(_ref_series(entries, _pred({"env": "prod"})))
    resp = db.search("t", req).response()
    got = json.loads(resp.metrics.agg_json)
    assert got == want
    # limit=1 truncates the result LIST but never the aggregate:
    # ?agg= disables the early-quit
    resp_lim = db.search("t", _mk_req({"env": "prod"},
                                      limit=1)).response()
    assert len(resp_lim.traces) == 1
    assert resp_lim.metrics.agg_json == resp.metrics.agg_json
    # breaker open: the host route serves the byte-identical aggregate
    robustness.BREAKER.reset()
    robustness.BREAKER.threshold = 1
    robustness.BREAKER.record_fault("timeout", mode="batched")
    assert robustness.BREAKER.state == "open"
    resp_h = db.search("t", _mk_req({"env": "prod"},
                                    limit=1000)).response()
    assert resp_h.metrics.agg_json == resp.metrics.agg_json
    robustness.BREAKER.reset()
    # a non-agg request through the same db carries no aggregate
    plain = tempopb.SearchRequest()
    plain.limit = 1000
    plain.tags["env"] = "prod"
    assert db.search("t", plain).response().metrics.agg_json == ""


def test_agg_concurrent_queries_match_serial(tmp_path):
    """Concurrent agg + non-agg queries through the coalescer: agg
    members launch solo, every answer byte-identical to serial."""
    entries = _corpus(43, n=100)
    db = _mkdb(tmp_path, entries, search_coalesce_window_s=0.05)
    reqs = [_mk_req({"env": "prod"}, limit=1000),
            _mk_req({"env": "dev"}, limit=1000),
            _mk_req({"service.name": "a"}, limit=1000),
            _mk_req({"env": "prod"}, limit=1000)]
    plain = tempopb.SearchRequest()
    plain.limit = 1000
    plain.tags["env"] = "prod"
    reqs.append(plain)

    def canon(resp):
        resp.metrics.device_seconds = 0
        return resp.SerializeToString()

    serial = [canon(db.search("t", tempopb.SearchRequest.FromString(
        r.SerializeToString())).response()) for r in reqs]
    out = [None] * len(reqs)
    barrier = threading.Barrier(len(reqs))

    def one(i):
        r = tempopb.SearchRequest.FromString(reqs[i].SerializeToString())
        barrier.wait()
        out[i] = canon(db.search("t", r).response())

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert out == serial


def test_http_agg_param_and_gate_400(tmp_path):
    from tempo_tpu.api.http import HTTPApi
    from tempo_tpu.modules import App, AppConfig
    from tempo_tpu.utils.test_data import make_trace

    app = App(AppConfig(
        wal_dir=str(tmp_path / "wal"),
        db=TempoDBConfig(auto_mesh=False,
                         search_analytics_enabled=True)))
    api = HTTPApi(app)
    hdr = {"X-Scope-OrgID": "t1"}
    for i in range(4):
        tid = bytes([i + 1]) * 16
        app.push("t1", list(make_trace(tid, seed=i).batches))
    api.handle("GET", "/flush", {}, hdr)
    app.reader_db.poll()
    code, body = api.handle("GET", "/api/search",
                            {"agg": "red", "limit": "10"}, hdr)
    assert code == 200, body
    agg = body.get("aggregates")
    assert agg and agg["type"] == "red"
    assert agg["buckets_ms"] == list(MS_BUCKETS)
    assert sum(s["calls"] for s in agg["series"].values()) == \
        len(body.get("traces", []))
    # the raw tag never leaks into the response metrics block
    assert "aggJson" not in body.get("metrics", {})
    # bad grammar: 400, not 500
    code, body = api.handle("GET", "/api/search",
                            {"agg": "p99", "limit": "10"}, hdr)
    assert code == 400 and "agg" in body["error"]
    # gate off: ?agg= is a 400, plain search still serves
    ANALYTICS.configure(enabled=False)
    code, body = api.handle("GET", "/api/search",
                            {"agg": "red", "limit": "10"}, hdr)
    assert code == 400 and "disabled" in body["error"]
    code, _body = api.handle("GET", "/api/search", {"limit": "10"}, hdr)
    assert code == 200
