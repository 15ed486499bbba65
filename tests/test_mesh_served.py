"""The served search path on a mesh, held to the plain reference.

What the cell `share16x4.scan` checks on four chips, at a small size on
the virtual CPU devices: a seeded `otel_blocks` corpus (the benchmark's
generator), searched through the HTTP handlers of one App whose
reader's batcher shards every staged group over a mesh of 1, 2, 4 or 8
devices; every answer is held to `chipbench/reference.py` by the
benchmark's own `check` (exact counts, `inspectedTraces`, match sets).
The cap is 12 pages a device, so the groups grow with the mesh (6 of 3
or 6 pages on one device, 15 and 6 pages on four, all 21 on eight) and
are ragged: padded to a power of two and to the mesh, so on every mesh
a shard's pages are partly or wholly padding. Then the merge across
shards under dense ties, solo and fused, against the full-sort order;
and what a mesh launch leaves in the counters and spans.
"""

import base64
import json
import urllib.parse
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import tracing

MESHES = (1, 2, 4, 8)
CORPUS = {
    "generator": "otel_blocks", "tenant": "meshtest", "config_name": "mesh",
    "blocks": 7, "entries_per_block": 3072,
    "services": 200, "routes": 500, "rpc_methods": 300, "pods": 2000,
    "customers": 10000, "span_names": 400, "zipf_s": 1.1,
    "dur_median_ms": 40, "dur_sigma": 1.787,
    "time_base": 1700000000, "time_span_s": 86400, "time_overlap": 0.1,
}
# the scan cell's two templates, and two that prune and fill
OPS = [
    {"op": "search", "variants": 3, "limit": 20,
     "tags": {"service.name": {"draw": "strata"},
              "http.status_code": {"fixed": "500"}},
     "min_duration_quantile": "0.9"},
    {"op": "search", "variants": 2, "limit": 20, "exhaustive": True,
     "tags": {"service.name": {"draw": "strata"}}},
    {"op": "search", "variants": 1, "limit": 20, "window_s": 20000,
     "tags": {"cloud.region": {"draw": "strata"}}},
    {"op": "search", "variants": 1, "limit": 5,
     "tags": {"http.method": {"fixed": "GET"}}},
]


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    from chipbench.generators import otel_blocks
    from chipbench.ops import search as op

    root = tmp_path_factory.mktemp("meshcorpus")
    with ThreadPoolExecutor(4) as pool:
        manifest = otel_blocks.generate(CORPUS, 2**31 + 26,
                                        str(root / "blocks"), pool)
    rng = np.random.default_rng(26)
    requests = [r for o in OPS for r in op.build(o, manifest, rng)]
    return {"dir": str(root), "manifest": manifest, "requests": requests}


PAGES = 12          # `search_max_batch_pages`: four 3-page blocks a device


def make_app(corpus, shards, tmp_path, pages=PAGES):
    """One App on the corpus whose reader shards over `shards` devices:
    what `TempoDB._ensure_mesh` does for all visible devices, for a
    mesh of a chosen size (1 = no mesh, as on a one-chip host). `pages`
    is `search_max_batch_pages`, pages per device: a group holds up to
    `pages * shards`."""
    from tempo_tpu.db.tempodb import TempoDBConfig
    from tempo_tpu.modules import App, AppConfig
    from tempo_tpu.parallel import make_mesh

    app = App(AppConfig(
        backend={"backend": "local",
                 "local": {"path": corpus["dir"] + "/blocks"}},
        wal_dir=str(tmp_path / "wal"),
        db=TempoDBConfig(auto_mesh=False, search_max_batch_pages=pages)))
    db = app.reader_db
    if shards > 1:
        db.mesh = make_mesh(shards)
        db.batcher.engine.mesh = db.mesh
        db.batcher.engine.n_shards = shards
    app.poll_tick()
    return app


def ask(api, request):
    path, _, qs = request["path"].partition("?")
    code, body = api.handle("GET", path, dict(urllib.parse.parse_qsl(qs)),
                            request["headers"])
    return {"status": code,
            "body": base64.b64encode(json.dumps(body).encode()).decode()}


@pytest.mark.parametrize("shards", MESHES)
def test_served_search_on_a_mesh_equals_the_reference(corpus, shards,
                                                     tmp_path):
    from chipbench.ops import search as op
    from tempo_tpu.api import HTTPApi

    app = make_app(corpus, shards, tmp_path)
    collector = tracing.CollectExporter()
    tracing.set_tracer(tracing.Tracer(tracing.SyncProcessor(collector)))
    modes = ("batched", "coalesced", "host_fallback")
    before = {m: obs.scan_dispatches.value(mode=m, shards=shards)
              for m in modes}
    elsewhere = sum(obs.scan_dispatches.value(mode=m) for m in modes) \
        - sum(before.values())
    try:
        api = HTTPApi(app, multitenancy=True)
        # solo first, then all at once: fused launches over the same groups
        answers = [ask(api, r) for r in corpus["requests"]]
        with ThreadPoolExecutor(len(corpus["requests"])) as pool:
            answers += list(pool.map(lambda r: ask(api, r),
                                     corpus["requests"]))
    finally:
        tracing.set_tracer(None)
        app.shutdown()
    for r, a in zip(corpus["requests"] * 2, answers):
        ok, why = op.check(r, a, corpus["manifest"])
        assert ok, (shards, r["path"], why)
    exhaustive = [json.loads(base64.b64decode(a["body"]))
                  for r, a in zip(corpus["requests"] * 2, answers)
                  if r["ref"].get("exhaustive")]
    assert exhaustive and all(
        int(d["metrics"]["inspectedTraces"]) == corpus["manifest"]["entries"]
        for d in exhaustive)

    # the counter: every launch under this mesh's `shards`, `mode` as ever
    moved = {m: obs.scan_dispatches.value(mode=m, shards=shards) - before[m]
             for m in modes}
    assert moved["batched"] + moved["coalesced"] > 0
    assert moved["host_fallback"] == 0
    assert sum(obs.scan_dispatches.value(mode=m) for m in modes) \
        - sum(obs.scan_dispatches.value(mode=m, shards=shards)
              for m in modes) == elsewhere
    text = obs.REGISTRY.expose()
    assert f'mode="batched",shards="{shards}"' in text
    assert 'mode="mesh"' not in "".join(
        ln for ln in text.splitlines()
        if ln.startswith("tempo_search_scan_dispatches_total"))

    # the spans: the launch and its kernel call say how it was sharded
    spans = [s for s in collector.spans if s.end_ns]
    launches = [s for s in spans if s.name == "coalescer.launch"]
    executes = [s for s in spans if s.name in ("dispatch.execute",
                                               "dispatch.compile")]
    assert launches and executes
    for s in launches + executes:
        assert s.attributes["shards"] == shards
        # a group's pages are padded to a power of two, at least one
        # page a shard
        staged = s.attributes["pages_per_shard"] * shards
        assert staged >= shards and staged & (staged - 1) == 0
        # ... and at most the cap on each device
        assert s.attributes["pages_per_shard"] <= PAGES
    if shards > 1:
        # the cap counts pages per device: a mesh's groups are wider
        # than one device's, and the answers above came from them
        assert max(s.attributes["pages_per_shard"]
                   for s in launches) * shards > PAGES
    waits = [s for s in spans if s.name == "dispatch.lock_wait"]
    if shards > 1:
        # one collective lock, and the wait for it is a span of its own
        # under the launch, so a search's trace still adds up
        assert len(waits) == len(launches)
        ids = {s.context.span_id for s in launches}
        assert all(s.parent_span_id in ids for s in waits)
        assert {s.attributes["mode"] for s in executes} == {"mesh"}
    else:
        assert not waits


def test_launches_per_search_fall_with_the_shards(corpus, tmp_path):
    """One search that scans the tenant, alone: a launch for each group,
    and the groups grow with the mesh (7 blocks of 3 pages under a cap
    of 12 pages a device: 6 groups, 3, 2), so four devices answer in a
    third of one device's launches, and answer the same."""
    from chipbench.ops import search as op
    from tempo_tpu.api import HTTPApi

    request = next(r for r in corpus["requests"]
                   if r["ref"].get("exhaustive"))
    launches, groups = {}, {}
    for shards in (1, 2, 4):
        app = make_app(corpus, shards, tmp_path / str(shards))
        collector = tracing.CollectExporter()
        tracing.set_tracer(tracing.Tracer(tracing.SyncProcessor(collector)))
        before = obs.scan_dispatches.value(shards=shards)
        try:
            answer = ask(HTTPApi(app, multitenancy=True), request)
        finally:
            tracing.set_tracer(None)
            app.shutdown()
        ok, why = op.check(request, answer, corpus["manifest"])
        assert ok, (shards, why)
        launches[shards] = obs.scan_dispatches.value(shards=shards) - before
        groups[shards] = max(s.attributes["groups"] for s in collector.spans
                             if s.name == "batcher.Search")
    assert launches == groups == {1: 6, 2: 3, 4: 2}


def _tied_blocks(n_blocks, per_block):
    from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
    from tempo_tpu.search.data import SearchData

    blocks = []
    for b in range(n_blocks):
        entries = []
        for i in range(per_block):
            sd = SearchData(trace_id=bytes([b, i]).rjust(16, b"\x00"))
            sd.start_s, sd.end_s, sd.dur_ms = 1_600_000_000, 1_600_000_005, 7
            sd.root_service, sd.root_name = "svc", "GET /"
            sd.kvs = {"service.name": {"svc"},
                      "parity": {"even" if i % 2 == 0 else "odd"}}
            entries.append(sd)
        blocks.append(ColumnarPages.build(entries, PageGeometry(32, 8)))
    return blocks


@pytest.mark.parametrize("queries", (1, 2, 4))
@pytest.mark.parametrize("shards", MESHES)
def test_ties_across_shards_merge_in_full_sort_order(shards, queries):
    """Every entry starts in the same second, so every score ties and
    the matches of one query lie on every shard (5 blocks of 4 pages;
    on 8 shards the 32 staged pages hold 12 of padding). The merge of
    the shards' candidates must return what one full stable sort by
    (score, flat index) returns: the lowest flat indices, whatever the
    mesh and however many queries share the launch."""
    from tempo_tpu import tempopb
    from tempo_tpu.parallel import make_mesh
    from tempo_tpu.search.engine import fetch_scan_out
    from tempo_tpu.search.multiblock import (
        MultiBlockEngine, compile_multi, stack_queries,
    )

    k = 64
    blocks = _tied_blocks(5, 100)
    reqs = []
    for tags in ({}, {"parity": "odd"}, {"parity": "even"},
                 {"service.name": "svc"})[:queries]:
        req = tempopb.SearchRequest()
        req.limit = k
        for key, val in tags.items():
            req.tags[key] = val
        reqs.append(req)
    mqs = [compile_multi(blocks, r) for r in reqs]
    eng = MultiBlockEngine(
        top_k=k, mesh=make_mesh(shards) if shards > 1 else None)
    batch = eng.stage(blocks)
    # the flat index of entry i of block b: pages are stacked block
    # after block, 4 pages of 32 entries a block
    flat = {"all": [b * 128 + i for b in range(5) for i in range(100)]}
    flat["odd"] = [f for f in flat["all"] if f % 128 % 2 == 1]
    flat["even"] = [f for f in flat["all"] if f % 128 % 2 == 0]
    want = [flat["all"], flat["odd"], flat["even"], flat["all"]][:queries]
    if queries == 1:
        count, _, scores, idx = eng.scan(batch, mqs[0])
        got = [(int(count), scores, idx)]
    else:
        counts, _, scores, idx = fetch_scan_out(
            eng.coalesced_scan_async(batch, stack_queries(mqs), k))
        got = [(int(counts[q]), scores[q], idx[q]) for q in range(queries)]
    for (count, scores, idx), w in zip(got, want):
        assert count == len(w)
        assert np.asarray(idx).tolist() == w[:k]
        assert len(set(np.asarray(scores).tolist())) == 1
