"""The served path of `GET /api/search?agg=red`: what the cell
`red16.dashboard` checks on the chip, at the configuration's `tiny` size
on the CPU.

A seeded `otel_red` corpus (tempo-search-red16's, cut by its own `tiny`:
12 blocks of 4,096 entries, the pair `error=true` on the 5xx entries)
behind one App with `search_analytics_enabled`. Every template of
`chipbench/traffic/dashboard.json` goes through the HTTP handlers and is
held to `chipbench/reference_red.py` by the benchmark's own `check`,
exactly, alone and from a burst of callers released together. Then who
shares a launch (the plain members of a burst fuse, its `?agg=` members
launch solo: `QueryCoalescer.submit` says why), the budget (the key column enters the cache's total once a
staged group, under two first searches too, and leaves with the group),
the gate, and the counters, spans and self-trace the cell's readers and
the dogfood loop read.
"""

import base64
import json
import os
import threading
import urllib.parse
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from conftest import check_budget, settle
from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import tracing
from tempo_tpu.search.batcher import BlockBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2**31 + 48


def _load(path):
    with open(os.path.join(ROOT, "chipbench", path)) as f:
        return json.load(f)


CONFIG = _load("configs/tempo-search-red16.json")
CORPUS = dict(CONFIG["corpus"], **CONFIG["tiny"]["corpus"],
              config_name="redtest")
TEMPLATES = {op["name"]: op for op in _load("traffic/dashboard.json")["ops"]}
PAGES_A_BLOCK = CORPUS["entries_per_block"] // 1024
KEY_ROW_BYTES = 4 * 1024      # a page of int32 keys


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    from chipbench.generators import otel_red

    root = tmp_path_factory.mktemp("redcorpus")
    with ThreadPoolExecutor(4) as pool:
        manifest = otel_red.generate(CORPUS, SEED, str(root / "blocks"), pool)
    return {"dir": str(root), "manifest": manifest}


def _app(corpus, tmp_path, **db):
    from tempo_tpu.db.tempodb import TempoDBConfig
    from tempo_tpu.modules import App, AppConfig

    db.setdefault("search_analytics_enabled", True)
    app = App(AppConfig(
        backend={"backend": "local",
                 "local": {"path": corpus["dir"] + "/blocks"}},
        wal_dir=str(tmp_path / "wal"),
        # a window wide enough that callers released together meet
        db=TempoDBConfig(auto_mesh=False, search_coalesce_window_s=0.05,
                         **db)))
    app.poll_tick()
    return app


@pytest.fixture
def app(corpus, tmp_path):
    """The whole tenant in one group, as the tiny rehearsal stages it."""
    app = _app(corpus, tmp_path)
    yield app
    app.shutdown()
    from tempo_tpu.search.analytics import ANALYTICS

    ANALYTICS.configure(enabled=False)


@pytest.fixture
def grouped(corpus, tmp_path, monkeypatch):
    """The same tenant in three groups of four blocks (only the cap
    closes a group: anchors are `test_group_cap.py`'s)."""
    monkeypatch.setattr(BlockBatcher, "_cuts",
                        staticmethod(lambda j, cap: False))
    app = _app(corpus, tmp_path, search_max_batch_pages=4 * PAGES_A_BLOCK)
    yield app
    app.shutdown()
    from tempo_tpu.search.analytics import ANALYTICS

    ANALYTICS.configure(enabled=False)


def api_of(app):
    from tempo_tpu.api import HTTPApi

    return HTTPApi(app, multitenancy=True)


def requests_of(corpus, template, seed=1, op="search_red"):
    import importlib

    mod = importlib.import_module(f"chipbench.ops.{op}")
    return mod.build(TEMPLATES[template], corpus["manifest"],
                     np.random.default_rng(seed))


def ask(api, request):
    path, _, qs = request["path"].partition("?")
    code, body = api.handle("GET", path, dict(urllib.parse.parse_qsl(qs)),
                            request["headers"])
    return {"status": code,
            "body": base64.b64encode(json.dumps(body).encode()).decode()}


def together(api, requests):
    """Every request on a thread of its own, released from one barrier."""
    gate = threading.Barrier(len(requests))

    def one(r):
        gate.wait()
        return ask(api, r)

    with ThreadPoolExecutor(len(requests)) as pool:
        return list(pool.map(one, requests))


def doc(answer):
    return json.loads(base64.b64decode(answer["body"]))


def held(corpus, requests, answers):
    from chipbench.ops import search_red as op

    for r, a in zip(requests, answers):
        ok, why = op.check(r, a, corpus["manifest"])
        assert ok, (r["path"], why)


def test_the_corpus_is_share16s_with_the_error_pair(corpus):
    """Every shape of tempo-search-share16 (the sixteen keys, 16 slots,
    the laws), one more key, and the error pair where the status is 5xx
    and nowhere else."""
    from chipbench.generators import otel_blocks as ob
    from chipbench.generators import otel_red

    m = corpus["manifest"]
    share16 = _load("configs/tempo-search-share16.json")
    for k, v in share16["corpus"].items():
        if k not in ("generator", "tenant"):
            assert CONFIG["corpus"][k] == v or k == "blocks", k
    assert CONFIG["corpus"]["blocks"] >= 64
    assert set(m["key_names"]) == set(ob.KEY_NAMES) | {"error"}
    assert m["kv_per_entry"] == 16
    assert ((m["vals"] >= 0).sum(axis=1) <= 16).all()
    status = m["vals"][:, m["key_names"].index("http.status_code"), :]
    is_5xx = np.isin(status, [m["table"].index(s)
                              for s in otel_red.ERROR_STATUS])
    assert (m["error"] == is_5xx).all()
    assert 0.005 < m["error"].mean() < 0.025       # 1.3 % by the law
    assert (m["root_service"] == m["vals"][
        :, m["key_names"].index("service.name"), :]).all()


@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_every_template_equals_the_reference_alone_and_in_a_burst(
        corpus, app, template):
    """Alone, then all of the template's variants released together:
    every caller's aggregate is its own, and its launches too (a
    reducing launch is a solo one: none in mode `coalesced`, and no
    program a lone search had not compiled)."""
    api = api_of(app)
    requests = requests_of(corpus, template)
    assert len(requests) == TEMPLATES[template]["variants"]
    held(corpus, requests, [ask(api, r) for r in requests])
    some = doc(ask(api, requests[0]))["aggregates"]
    assert some["type"] == "red" and len(some["buckets_ms"]) == 14
    solo = obs.agg_launches.value(mode="batched")
    on_host = obs.scan_dispatches.value(mode="host_fallback")
    misses = obs.jit_cache_events.value(result="miss")
    burst = (requests * 2)[:max(4, len(requests))]
    held(corpus, burst, together(api, burst))
    assert obs.agg_launches.value(mode="batched") - solo == len(burst)
    assert obs.agg_launches.value(mode="coalesced") == 0
    assert obs.jit_cache_events.value(result="miss") == misses
    assert obs.scan_dispatches.value(mode="host_fallback") == on_host
    assert settle(app.reader_db.batcher) == 0


def test_agg_and_plain_members_of_one_burst_both_answer_right(corpus, app):
    """Plain members fuse with one another, `?agg=` members launch
    solo: every answer is right for its kind, and a plain answer carries
    no aggregate."""
    from chipbench.ops import search

    api = api_of(app)
    red = requests_of(corpus, "red-region")
    plain = requests_of(corpus, "red-region", op="search")
    assert [r["ref"] for r in red] == [r["ref"] for r in plain]
    for r in plain:                # a lone search compiles its program
        ask(api, r)
    fused = obs.scan_dispatches.value(mode="coalesced")
    answers = together(api, red + plain)
    assert obs.scan_dispatches.value(mode="coalesced") > fused
    assert obs.agg_launches.value(mode="coalesced") == 0
    held(corpus, red, answers[:len(red)])
    m = dict(corpus["manifest"])
    m.pop("_search_reference", None)   # the plain op's own memo: a plain
    for r, a in zip(plain, answers[len(red):]):    # search may stop early
        ok, why = search.check(r, a, m)
        assert ok, why
        assert "aggregates" not in doc(a)


def columns_built() -> float:
    """Key columns built so far: the observations of
    `tempo_search_agg_stage_seconds`, as `/metrics` shows them."""
    from chipbench.lib import metric_sum, parse_metrics

    return metric_sum(parse_metrics(obs.REGISTRY.expose()),
                      "tempo_search_agg_stage_seconds_count")


def _first_searches(corpus, api, n):
    r = requests_of(corpus, "red-env")[0]
    answers = together(api, [r] * n)
    held(corpus, [r] * n, answers)


@pytest.mark.parametrize("callers", (1, 2))
def test_the_key_column_enters_the_budget_once_and_leaves_with_its_group(
        corpus, grouped, callers):
    """A group's first `?agg=` search stages its key column: the cache's
    total, the gauge and `tempo_search_hbm_cache_bytes` rise by 4 B a
    staged entry, once a group whether one or two searches came first;
    a second search adds nothing; an evicted group gives it back."""
    api = api_of(grouped)
    cache = grouped.reader_db.batcher.cache
    plain = requests_of(corpus, "red-env", op="search")[0]
    plain["path"] = plain["path"].replace("limit=20", "limit=100000")
    assert ask(api, plain)["status"] == 200      # stages the three groups
    assert settle(grouped.reader_db.batcher) == 0
    before = check_budget(cache)
    assert len(before) == 3 and obs.agg_staged_bytes.value() == 0
    total = cache.snapshot()["hbm_bytes"]
    column = 4 * PAGES_A_BLOCK * KEY_ROW_BYTES   # a group's, no pad pages
    _first_searches(corpus, api, callers)
    assert settle(grouped.reader_db.batcher) == 0
    now = check_budget(cache)
    assert {k: n - before[k][0] for k, (n, _p, _m) in now.items()} == {
        k: column for k in before}
    assert cache.snapshot()["hbm_bytes"] == total + 3 * column
    assert obs.agg_staged_bytes.value() == 3 * column
    assert obs.hbm_cache_bytes.value() == total + 3 * column
    assert all(cache.resident(k).agg_bytes == column for k in now)
    _first_searches(corpus, api, 2)              # resident: nothing more
    assert cache.snapshot()["hbm_bytes"] == total + 3 * column
    victim = next(iter(now))
    with cache.group_lock:
        cache._drop_hbm_locked(victim)
        cache._publish_gauges_locked()
    assert obs.agg_staged_bytes.value() == 2 * column
    assert cache.snapshot()["hbm_bytes"] == total + 3 * column - now[
        victim][0]
    check_budget(cache)
    _first_searches(corpus, api, 1)              # staged again: charged
    assert settle(grouped.reader_db.batcher) == 0
    assert obs.agg_staged_bytes.value() == 3 * column
    check_budget(cache)


def test_two_first_searches_build_the_column_once(corpus, grouped):
    """One flight: of two searches that arrive together at groups nobody
    has aggregated over, one builds each group's column
    (`tempo_search_agg_stage_seconds` observes three, not six) and one
    writes its `analytics.stage`."""
    api = api_of(grouped)
    exporter = tracing.CollectExporter()
    tracing.set_tracer(tracing.Tracer(tracing.SyncProcessor(exporter)))
    try:
        built = columns_built()
        _first_searches(corpus, api, 2)
        assert columns_built() - built == 3
    finally:
        tracing.set_tracer(None)
    stages = [s for s in exporter.spans if s.name == "analytics.stage"]
    assert len(stages) == 3
    assert all(s.attributes["bytes"] == 4 * PAGES_A_BLOCK * KEY_ROW_BYTES
               and s.attributes["blocks"] == 4 for s in stages)


def test_gate_off_gives_400_and_plain_search_is_untouched(corpus, tmp_path):
    app = _app(corpus, tmp_path, search_analytics_enabled=False)
    try:
        api = api_of(app)
        red = requests_of(corpus, "red-env")[0]
        a = ask(api, red)
        assert a["status"] == 400 and "disabled" in json.dumps(doc(a))
        plain = requests_of(corpus, "red-env", op="search")[0]
        a = ask(api, plain)
        assert a["status"] == 200 and "aggregates" not in doc(a)
        assert obs.agg_staged_bytes.value() == 0
    finally:
        app.shutdown()


def test_the_counters_and_spans_the_cells_readers_read(corpus, app):
    """A served `?agg=` search moves the four series and writes the
    three spans and `agg_keys`; a plain search moves and writes none of
    them."""
    api = api_of(app)
    red = requests_of(corpus, "red-team")[0]
    plain = requests_of(corpus, "red-team", op="search")[0]
    ask(api, red)                                  # stage, compile

    def traced(request):
        exporter = tracing.CollectExporter()
        tracing.set_tracer(tracing.Tracer(tracing.SyncProcessor(exporter)))
        try:
            c0 = (obs.agg_launches.value(), obs.agg_key_rows.value())
            assert ask(api, request)["status"] == 200
            moved = (obs.agg_launches.value() - c0[0],
                     obs.agg_key_rows.value() - c0[1])
        finally:
            tracing.set_tracer(None)
        return exporter.spans, moved

    spans, (launches, rows) = traced(red)
    names = [s.name for s in spans]
    rows_staged = CORPUS["blocks"] * CORPUS["entries_per_block"]
    assert launches == 1 and rows >= rows_staged   # pad pages included
    assert rows % 1024 == 0
    assert names.count("analytics.decode") == 1
    assert names.count("results.merge_agg") >= 1
    assert "analytics.stage" not in names          # resident: staged before
    keyed = [s for s in spans if "agg_keys" in s.attributes]
    assert keyed and all(s.name.startswith("dispatch.") for s in keyed)
    assert {s.attributes["agg_keys"] for s in keyed} == {256 * 15 * 2}
    text = obs.REGISTRY.expose()
    for name in ("tempo_search_agg_launches_total",
                 "tempo_search_agg_key_rows_total",
                 "tempo_search_agg_staged_bytes",
                 "tempo_search_agg_stage_seconds"):
        assert f"# TYPE {name} " in text
    assert obs.agg_staged_bytes.value() > 0

    spans, moved = traced(plain)
    assert moved == (0, 0)
    assert not {s.name for s in spans} & {
        "analytics.stage", "analytics.decode", "results.merge_agg"}
    assert not [s for s in spans if "agg_keys" in s.attributes]


def test_a_served_agg_searchs_self_trace_is_found_by_service_name(
        corpus, tmp_path):
    """The dogfood loop: an entry keeps 64 (key, value) pairs in key
    order and the aggregate's spans and `agg_keys` sort before
    `service.name`: the self-trace of a served `?agg=` search is still
    found by it."""
    from tempo_tpu.db.tempodb import TempoDBConfig
    from tempo_tpu.modules import App, AppConfig
    from tempo_tpu.observability import selftrace
    from tempo_tpu.observability.flightrecorder import RECORDER
    from tempo_tpu.observability.tracing import SELFTRACE_TENANT
    from tempo_tpu.search.analytics import ANALYTICS

    app = App(AppConfig(
        backend={"backend": "local",
                 "local": {"path": corpus["dir"] + "/blocks"}},
        wal_dir=str(tmp_path / "wal"),
        db=TempoDBConfig(auto_mesh=False, search_analytics_enabled=True),
        self_tracing={"enabled": True, "exporter": "self",
                      "selftrace_ingest_enabled": True, "sample_ratio": 1.0,
                      "flush_interval_s": 0.05}))
    try:
        app.poll_tick()
        api = api_of(app)
        red = requests_of(corpus, "red-errors")[0]
        for _ in range(2):      # the second is a jit hit: `execute`
            a = ask(api, red)
            assert a["status"] == 200
        held(corpus, [red], [a])
        app.tracer.processor.force_flush()
        app.flush_tick(force=True)
        app.poll_tick()
        hdr = {"X-Scope-OrgID": SELFTRACE_TENANT}
        code, body = api.handle(
            "GET", "/api/search",
            {"tags": "service.name=tempo-tpu", "limit": "50"}, hdr)
        assert code == 200
        found = []
        for hit in body.get("traces") or []:
            code, trace = api.handle(
                "GET", f"/api/traces/{hit['traceId']}", {}, hdr)
            assert code == 200
            found.append(json.dumps(trace))
        mine = [t for t in found if "analytics.decode" in t]
        assert mine, "no self-trace of the ?agg= search under service.name"
        assert any("agg_keys" in t and "results.merge_agg" in t for t in mine)
    finally:
        app.shutdown()
        tracing.set_tracer(None)
        selftrace.configure(ingest_enabled=False, flight_recorder_max=32)
        RECORDER.reset()
        ANALYTICS.configure(enabled=False)
