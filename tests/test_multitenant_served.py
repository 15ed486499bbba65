"""The served search path with several tenants in one process: what the
cell `tenants32.scan` checks on the chip, at a small size on the CPU.

A seeded `otel_tenants` corpus of three tenants of 7, 5 and 3 blocks
(one group each: block-axis buckets 8, 8 and 4) behind one App. Every
answer goes through the HTTP handlers under its `X-Scope-OrgID` and is
held to `chipbench/reference.py` over THAT tenant's arrays by the
benchmark's own `check`. Then isolation (the same predicate, three
answers, no trace id of another tenant's block), who may share a fused
launch (callers on one tenant, never callers on two), and that the
counter, the gauge and the span attributes the cell's readers read are
there.
"""

import base64
import json
import threading
import urllib.parse
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import tracing

SIZES = [7, 5, 3]
CORPUS = {
    "generator": "otel_tenants", "config_name": "tenantstest",
    "tenant_prefix": "team", "tenants": 3, "tenant_zipf_s": 0.7,
    "tenant_blocks": SIZES, "blocks": sum(SIZES),
    "entries_per_block": 2 * 1024,
    "services": 200, "routes": 500, "rpc_methods": 300, "pods": 2000,
    "customers": 10000, "span_names": 400, "zipf_s": 1.1,
    "dur_median_ms": 40, "dur_sigma": 1.787,
    "time_base": 1700000000, "time_span_s": 86400, "time_overlap": 0.1,
}
TEMPLATES = {
    "errors-slow": {"tags": {"service.name": {"draw": "strata"},
                             "http.status_code": {"fixed": "500"}},
                    "min_duration_quantile": "0.9", "limit": 20},
    "exhaustive": {"tags": {"service.name": {"draw": "strata"}},
                   "exhaustive": True, "limit": 20},
}


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    from chipbench.generators import otel_tenants

    root = tmp_path_factory.mktemp("tenantscorpus")
    with ThreadPoolExecutor(4) as pool:
        manifest = otel_tenants.generate(CORPUS, 2**31 + 40,
                                         str(root / "blocks"), pool)
    return {"dir": str(root), "manifest": manifest}


@pytest.fixture
def app(corpus, tmp_path):
    from tempo_tpu.db.tempodb import TempoDBConfig
    from tempo_tpu.modules import App, AppConfig

    app = App(AppConfig(
        backend={"backend": "local",
                 "local": {"path": corpus["dir"] + "/blocks"}},
        wal_dir=str(tmp_path / "wal"),
        # a window wide enough that callers released together meet
        db=TempoDBConfig(auto_mesh=False, search_coalesce_window_s=0.05)))
    app.poll_tick()
    yield app
    app.shutdown()


def predicates(corpus, template, seed=1, variants=2):
    """`variants` requests of one template, each once for every tenant:
    the same predicate under three `X-Scope-OrgID`s."""
    from chipbench.ops import search

    m = corpus["manifest"]
    base = search.build(dict(TEMPLATES[template], variants=variants), m,
                        np.random.default_rng(seed))
    return [[dict(r, headers={"X-Scope-OrgID": t}) for t in m["tenants"]]
            for r in base]


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


def test_the_tenants_are_sized_by_the_law_and_laid_out_tenant_major(corpus):
    from chipbench.generators import otel_tenants

    m = corpus["manifest"]
    assert m["tenants"] == ["team1", "team2", "team3"]
    assert list(m["blocks"].values()) == SIZES
    assert m["tenant_slice"] == {"team1": (0, 7), "team2": (7, 12),
                                 "team3": (12, 15)}
    assert m["tenant_class"] == {"team1": 8, "team2": 8, "team3": 4}
    # the configuration's 32 tenants: 625 blocks, the smallest holds 4
    sizes = otel_tenants.tenant_sizes(625, 32, 1.1)
    assert sum(sizes) == 625 and sizes[0] == 177 and min(sizes) == 4
    assert sorted(sizes, reverse=True) == sizes


@pytest.mark.parametrize("template", sorted(TEMPLATES))
def test_one_predicate_three_tenants_three_answers(corpus, app, template):
    """Each answer equals its own tenant's reference and holds no trace
    id of another tenant's block; an exhaustive search inspects exactly
    its tenant's entries, pad rows and other tenants' none."""
    from chipbench.generators.otel_blocks import entry_of_trace_id
    from chipbench.ops import search_tenant as op
    from tempo_tpu.api import HTTPApi

    api = HTTPApi(app, multitenancy=True)
    m = corpus["manifest"]
    on_host = obs.scan_dispatches.value(mode="host_fallback")
    for same in predicates(corpus, template):
        answers = [ask(api, r) for r in same]
        inspected = []
        for r, a in zip(same, answers):
            ok, why = op.check(r, a, m)
            assert ok, (r["headers"], r["path"], why)
            lo, hi = m["tenant_slice"][r["headers"]["X-Scope-OrgID"]]
            for t in doc(a).get("traces", []):
                block, _entry = entry_of_trace_id(t["traceId"])
                assert lo <= block < hi
            inspected.append(int(doc(a)["metrics"]["inspectedTraces"]))
        if template == "exhaustive":
            assert inspected == [n * CORPUS["entries_per_block"]
                                 for n in SIZES]
    # the check is not vacuous: another tenant's answer is refused
    a, b = same[0], same[1]
    ok, why = op.check(a, ask(api, b), m)
    assert not ok
    assert obs.scan_dispatches.value(mode="host_fallback") == on_host


def test_callers_on_different_tenants_never_share_a_launch(corpus, app):
    """Nine callers released together, three a tenant: every fused
    launch's members are searches of ONE tenant (a launch is keyed on
    the staged batch, a tenant's own), callers on one tenant do share
    launches, and every answer is still its tenant's reference."""
    from chipbench.ops import search_tenant as op
    from tempo_tpu.api import HTTPApi

    api = HTTPApi(app, multitenancy=True)
    m = corpus["manifest"]
    requests = [r for same in predicates(corpus, "exhaustive", seed=2,
                                         variants=3) for r in same]
    for r in requests:                 # stage, compile, fill the memo
        assert ask(api, r)["status"] == 200
    exporter = tracing.CollectExporter()
    tracing.set_tracer(tracing.Tracer(tracing.SyncProcessor(exporter)))
    try:
        fused = 0
        for _ in range(5):
            before = obs.coalesced_queries.value()
            answers = together(api, requests)
            for r, a in zip(requests, answers):
                ok, why = op.check(r, a, m)
                assert ok, (r["headers"], why)
            fused += obs.coalesced_queries.value() - before
            if fused:
                break
    finally:
        tracing.set_tracer(None)
    assert fused, "no two callers on one tenant ever met in a launch"
    spans = list(exporter.spans)
    tenant_of = {s.context.span_id: s.attributes.get("tenant")
                 for s in spans if s.name == "batcher.Search"}
    assert set(tenant_of.values()) == set(m["tenants"])
    members: dict = {}
    for s in spans:
        if s.name == "coalescer.wait":
            members.setdefault(s.attributes["launch"], []).append(
                tenant_of[s.parent_span_id])
    assert any(len(ts) > 1 for ts in members.values())
    for launch, ts in members.items():
        assert len(set(ts)) == 1, (launch, ts)


def test_the_cells_counter_gauge_and_attributes_are_there(corpus, app):
    from tempo_tpu.api import HTTPApi
    from tempo_tpu.observability.metrics import REGISTRY

    api = HTTPApi(app, multitenancy=True)
    m = corpus["manifest"]
    rows = {k: obs.launch_table_rows.value(kind=k) for k in ("real", "pad")}
    exporter = tracing.CollectExporter()
    tracing.set_tracer(tracing.Tracer(tracing.SyncProcessor(exporter)))
    try:
        for r in predicates(corpus, "errors-slow", seed=3, variants=1)[0]:
            assert ask(api, r)["status"] == 200
    finally:
        tracing.set_tracer(None)
    # one solo launch a tenant: 7 + 5 + 3 rows, padded to 8 + 8 + 4
    assert obs.launch_table_rows.value(kind="real") - rows["real"] == 15
    assert obs.launch_table_rows.value(kind="pad") - rows["pad"] == 5
    assert obs.scan_jit_keys.value() >= 2    # the 8-row and the 4-row shape
    text = REGISTRY.expose()
    assert "# TYPE tempo_search_launch_table_rows_total counter" in text
    assert "# TYPE tempo_search_scan_jit_keys gauge" in text
    by_name: dict = {}
    for s in exporter.spans:
        by_name.setdefault(s.name, []).append(s.attributes)
    assert sorted(a["tenant"] for a in by_name["batcher.Search"]) \
        == m["tenants"]
    want = sorted(zip(SIZES, [8, 8, 4]))
    # a shape's first launch in the process is a `dispatch.compile`
    kernel_calls = by_name.get("dispatch.execute", []) + by_name.get(
        "dispatch.compile", [])
    for name, spans in (("coalescer.launch", by_name["coalescer.launch"]),
                        ("dispatch.execute", kernel_calls)):
        launched = [a for a in spans if "blocks" in a]
        assert sorted((a["blocks"], a["blocks_bucket"])
                      for a in launched) == want, name


def test_callers_connect_while_the_accept_thread_waits_its_turn(app):
    """Forty callers open a connection before the server accepts one, as
    when its accept thread is behind sixteen searching threads: none is
    left to the kernel's one-second retransmission (a backlog of the
    stdlib's 5 holds six and drops the seventh's SYN)."""
    import socket

    from tempo_tpu.api import HTTPApi, serve_http

    server = serve_http(HTTPApi(app, multitenancy=True), host="127.0.0.1",
                        port=0)          # listening; nothing accepts yet
    socks = []
    try:
        for _ in range(40):
            s = socket.socket()
            s.settimeout(0.5)
            socks.append(s)
            s.connect(server.server_address)
    finally:
        for s in socks:
            s.close()
        server.server_close()
