"""The served search path with HBM as a cache: a tenant several times
its staged-batch budget, held to the plain reference while its groups
are evicted and staged again between and during searches.

What the cell `share16.evict` checks on the chip, at a small size on
the CPU: a seeded `otel_blocks` corpus of 48 blocks over 24 h (half an
hour a block), a cap of 8 pages a group (4 blocks, two hours: 12 groups
of 0.5 MB) and a budget of 1.2 MB, which holds two. Every answer goes
through the HTTP handlers of one App and is held to
`chipbench/reference.py` by the benchmark's own `check`. Then what the
budget promises (the cache's high water under concurrent tenant-wide
searches), what a window costs (the groups its hours lie in, not all of
them), and that the plan is the same jobs whatever order they come in.
"""

import base64
import json
import random
import threading
import time
import urllib.parse
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from conftest import check_budget, settle
from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import tracing
from tempo_tpu.search.batcher import BlockBatcher, ScanJob

BLOCKS, PAGES_A_BLOCK, CAP = 48, 2, 8
BUDGET = 1_200_000
CORPUS = {
    "generator": "otel_blocks", "tenant": "evicttest", "config_name": "evict",
    "blocks": BLOCKS, "entries_per_block": PAGES_A_BLOCK * 1024,
    "services": 200, "routes": 500, "rpc_methods": 300, "pods": 2000,
    "customers": 10000, "span_names": 400, "zipf_s": 1.1,
    "dur_median_ms": 40, "dur_sigma": 1.787,
    "time_base": 1700000000, "time_span_s": 86400, "time_overlap": 0.1,
}
HUNT = {"op": "search", "variants": 2, "limit": 20,
        "tags": {"service.name": {"draw": "strata"},
                 "http.status_code": {"fixed": "500"}},
        "min_duration_quantile": "0.9"}
NEWEST = CORPUS["time_base"] + CORPUS["time_span_s"]
HOUR = 3600


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    from chipbench.generators import otel_blocks

    root = tmp_path_factory.mktemp("evictcorpus")
    with ThreadPoolExecutor(4) as pool:
        manifest = otel_blocks.generate(CORPUS, 2**31 + 30,
                                        str(root / "blocks"), pool)
    return {"dir": str(root), "manifest": manifest}


def hunts(corpus, seed, **template):
    from chipbench.ops import search as op

    return op.build(dict(HUNT, **template), corpus["manifest"],
                    np.random.default_rng(seed))


def windowed(requests, age_h, hours):
    """The requests over the `hours` that end `age_h` hours before the
    newest data, as `chipbench/ops/search_aged.py` places a window."""
    end = int(NEWEST - age_h * HOUR)
    out = []
    for r in requests:
        r = dict(r, ref=dict(r["ref"], start=end - hours * HOUR, end=end))
        r["path"] += f"&start={end - hours * HOUR}&end={end}"
        out.append(r)
    return out


KINDS = {
    "one-group-window": lambda c: [
        r for age in (0, 7, 15, 22) for r in windowed(hunts(c, age), age, 2)],
    "six-hour-window": lambda c: [
        r for age in (0, 9, 18) for r in windowed(hunts(c, age), age, 6)],
    "whole-tenant": lambda c: windowed(hunts(c, 3, variants=3), 0, 24),
    "exhaustive": lambda c: hunts(c, 4, exhaustive=True),
}


def _app(corpus, tmp_path, monkeypatch, budget):
    from tempo_tpu.db.tempodb import TempoDBConfig
    from tempo_tpu.modules import App, AppConfig

    monkeypatch.setattr(BlockBatcher, "_cuts",
                        staticmethod(lambda j, cap: False))
    app = App(AppConfig(
        backend={"backend": "local",
                 "local": {"path": corpus["dir"] + "/blocks"}},
        wal_dir=str(tmp_path / "wal"),
        db=TempoDBConfig(auto_mesh=False, search_max_batch_pages=CAP,
                         search_batch_cache_bytes=budget)))
    app.poll_tick()
    return app


@pytest.fixture
def app(corpus, tmp_path, monkeypatch):
    """One App on the corpus: groups of four blocks (only the cap closes
    a group: anchors are `test_group_cap.py`'s), a budget of two."""
    app = _app(corpus, tmp_path, monkeypatch, BUDGET)
    yield app
    app.shutdown()


@pytest.fixture
def roomy(corpus, tmp_path, monkeypatch):
    """The same App with a budget that holds the whole tenant."""
    app = _app(corpus, tmp_path, monkeypatch, 16 * BUDGET)
    yield app
    app.shutdown()


def ask(api, request):
    path, _, qs = request["path"].partition("?")
    code, body = api.handle("GET", path, dict(urllib.parse.parse_qsl(qs)),
                            request["headers"])
    return {"status": code,
            "body": base64.b64encode(json.dumps(body).encode()).decode()}


def events(result):
    return obs.batch_cache_events.value(result=result)


@pytest.mark.parametrize("callers", (3, 8))
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_served_answers_under_eviction_equal_the_reference(corpus, app,
                                                           kind, callers):
    """Alone and then `callers` at a time (eight: eight tenant-wide
    searches walk twelve groups through a budget of two together, each
    by what is resident at its every step), each kind after a
    tenant-wide search has pushed its groups out: every answer is the
    reference's, groups were evicted and staged again while it was made,
    and nothing stays pinned."""
    from chipbench.ops import search as op
    from tempo_tpu.api import HTTPApi

    api = HTTPApi(app, multitenancy=True)
    requests = KINDS[kind](corpus)
    flush = windowed(hunts(corpus, 99, variants=1), 0, 24)[0]
    assert ask(api, flush)["status"] == 200
    evicted, missed = events("evict"), events("miss")
    on_host = obs.scan_dispatches.value(mode="host_fallback")
    answers = []
    for r in requests:
        answers.append(ask(api, r))
        ask(api, flush)
    together = [requests[i % len(requests)]
                for i in range(max(callers, 2 * len(requests)))]
    with ThreadPoolExecutor(callers) as pool:
        answers += list(pool.map(lambda r: ask(api, r), together))
    for r, a in zip(requests + together, answers):
        ok, why = op.check(r, a, corpus["manifest"])
        assert ok, (kind, r["path"], why)
    assert events("evict") > evicted and events("miss") > missed
    batcher = app.reader_db.batcher
    assert settle(batcher) == 0
    assert obs.scan_dispatches.value(mode="host_fallback") == on_host
    if kind == "exhaustive":
        docs = [json.loads(base64.b64decode(a["body"])) for a in answers]
        assert all(int(d["metrics"]["inspectedTraces"])
                   == corpus["manifest"]["entries"] for d in docs)


@pytest.mark.parametrize("callers", (1, 2, 8))
def test_the_cache_stands_over_budget_by_what_is_in_flight(corpus, app,
                                                           callers,
                                                           monkeypatch):
    """Tenant-wide searches, `callers` at a time, over 12 groups against
    a budget of 2: the high water of the cache is at most the budget
    plus `pipeline_depth` + 1 groups for each caller (one caller: 2.7 of
    the tenant's 6 MB), never the tenant, which search-long pins held
    whole however few the callers (eight walk it together and read 7-8
    groups of the 12), and once they are done the cache is back under
    its budget. One caller stands over the budget only while its
    look-ahead's group is in beside the two it holds: its look-ahead's
    put is made to land before the search goes on to the drain that
    gives one of them back, as on an idle machine it does."""
    from concurrent.futures import wait

    from chipbench.ops import search as op
    from tempo_tpu.api import HTTPApi

    api = HTTPApi(app, multitenancy=True)
    batcher = app.reader_db.batcher
    submit = batcher._prefetcher.submit

    def landed(fn, *args):
        fut = submit(fn, *args)
        wait([fut], 30)
        return fut

    if callers == 1:
        monkeypatch.setattr(batcher._prefetcher, "submit", landed)
    requests = windowed(hunts(corpus, 5, variants=callers), 0, 24)
    for _ in range(2):
        missed = events("miss")
        with ThreadPoolExecutor(callers) as pool:
            answers = list(pool.map(lambda r: ask(api, r), requests))
        for r, a in zip(requests, answers):
            ok, why = op.check(r, a, corpus["manifest"])
            assert ok, (r["path"], why)
    assert settle(batcher) == 0
    # callers walk the tenant together: a group is staged for all of
    # them, not once for each (their own uncounted copies)
    assert events("miss") - missed <= 3 * (BLOCKS // 4)
    cache = batcher.cache
    snap = cache.snapshot()
    group = max(n for n, _p, _m in snap["entries"].values())
    total, peak = snap["hbm_bytes"], snap["hbm_peak_bytes"]
    tenant = BLOCKS // 4 * group
    allowance = callers * (batcher.pipeline_depth + 1) * group
    assert BUDGET < peak <= BUDGET + allowance
    assert peak < tenant
    assert total <= BUDGET
    assert obs.hbm_cache_peak_bytes.value() >= peak
    assert obs.hbm_cache_bytes.value() == total


@pytest.mark.parametrize("age_h", (0, 11, 22))
def test_a_window_takes_the_groups_its_hours_lie_in(corpus, app, age_h):
    """A two-hour window is one group long: it takes one to three
    groups of the twelve (its hours, the 10 % overlap of their
    neighbours), and the header prune skips the others before staging."""
    from chipbench.ops import search as op
    from tempo_tpu.api import HTTPApi

    api = HTTPApi(app, multitenancy=True)
    (r,) = windowed(hunts(corpus, 6, variants=1), age_h, 2)
    before = events("hit") + events("miss")
    a = ask(api, r)
    ok, why = op.check(r, a, corpus["manifest"])
    assert ok, why
    assert 1 <= events("hit") + events("miss") - before <= 3
    doc = json.loads(base64.b64decode(a["body"]))
    assert int(doc["metrics"]["skippedBlocks"]) >= BLOCKS - 12


def test_a_group_staged_among_pinned_ones_keeps_its_place(corpus, app):
    """Three groups taken against a budget of two, none given back: the
    third is not evicted by its own insert (it would be scanned as a
    copy the budget no longer counts, and staged again by the next
    search: on a v5e that filled the chip), the cache stands over its
    budget by exactly what is pinned and says so, and it is back under
    the budget once the pins go."""
    from tempo_tpu.api import HTTPApi

    api = HTTPApi(app, multitenancy=True)
    (r,) = windowed(hunts(corpus, 9, variants=1), 0, 2)
    assert ask(api, r)["status"] == 200           # makes the plan
    batcher = app.reader_db.batcher
    assert settle(batcher) == 0
    (*_job_lists, groups), = app.reader_db._breq_jobs_cache.values()
    cache = batcher.cache
    taken = [cache.staged(g, pin=True) for g in groups[:3]]
    keys = [tuple(j.key for j in g) for g in groups[:3]]
    assert [cache.resident(k) for k in keys] == taken
    assert [c.pins for c in taken] == [1, 1, 1]
    held = sum(c.nbytes for c in taken)
    assert BUDGET < held <= cache.snapshot()["hbm_bytes"]
    assert cache.snapshot()["hbm_peak_bytes"] >= held
    with cache.group_lock:
        cache.unpin_locked(taken)
    assert cache.snapshot()["hbm_bytes"] <= BUDGET
    assert [c.pins for c in taken] == [0, 0, 0]
    assert cache.resident(keys[2]) is taken[2]   # the newest stays


def _jobs(n, rng):
    """Blocks written in time order under ids that say nothing of it."""
    return [ScanJob(key=(f"{rng.getrandbits(128):032x}", 0, 2),
                    pages_fn=None, header={"min_start_s": 1000 + 60 * i,
                                           "max_end_s": 1070 + 60 * i},
                    n_pages=2, n_entries=2048, geometry=(1024, 16))
            for i in range(n)]


@pytest.mark.parametrize("shards", (1, 2, 4))
def test_a_plan_is_the_same_jobs_whatever_their_order(shards):
    """Every job once, the same groups whatever order the jobs come in,
    in time order within and across groups; a job without a header time
    sorts first, by its key, as all jobs did before."""
    rng = random.Random(30)
    b = BlockBatcher(max_batch_pages=16)
    b.engine.n_shards = shards
    jobs = _jobs(200, rng)
    untimed = [ScanJob(key=(f"old-{i:03d}", 0, 2), pages_fn=None, header={},
                       n_pages=2, n_entries=2048, geometry=(1024, 16))
               for i in range(5)]
    want = b.plan(jobs + untimed)
    flat = [j for g in want for j in g]
    assert sorted(j.key for j in flat) == sorted(
        j.key for j in jobs + untimed)
    assert flat[:5] == untimed and flat[5:] == jobs
    assert max(sum(j.n_pages for j in g) for g in want) <= 16 * shards
    for _ in range(3):
        shuffled = jobs + untimed
        rng.shuffle(shuffled)
        got = b.plan(shuffled)
        assert [[j.key for j in g] for g in got] == [
            [j.key for j in g] for g in want]
    # a window of consecutive blocks lies in a few consecutive groups
    # (in id order its ten blocks would lie in up to ten of them)
    live = {j.key for j in jobs[90:100]}
    touched = [i for i, g in enumerate(want)
               if any(j.key in live for j in g)]
    assert touched == list(range(touched[0], touched[-1] + 1))
    assert len(touched) <= 4 and 3 * len(touched) <= len(want)


def test_an_early_quit_gives_its_look_ahead_back(corpus, app):
    """A search that fills its limit in the first group leaves a
    look-ahead staging the next: its pin goes when it finishes, and the
    answer is `limit` true matches."""
    from chipbench.ops import search as op
    from tempo_tpu.api import HTTPApi

    api = HTTPApi(app, multitenancy=True)
    (r,) = op.build({"op": "search", "variants": 1, "limit": 5,
                     "tags": {"http.method": {"fixed": "GET"}}},
                    corpus["manifest"], np.random.default_rng(7))
    for _ in range(3):
        a = ask(api, r)
        ok, why = op.check(r, a, corpus["manifest"])
        assert ok, why
    batcher = app.reader_db.batcher
    assert settle(batcher) == 0
    assert batcher.cache.snapshot()["hbm_bytes"] <= BUDGET


def test_the_prepare_memo_outlives_the_hbm_copy(corpus, app):
    """A tenant-wide search twice over 12 groups against a budget of 2:
    the second pass stages ten of them again and compiles nothing, its
    memo came back from the host tier with the group (without the
    predicate's uploaded tables, which were HBM), and the answer is the
    reference's both times."""
    from chipbench.ops import search as op
    from tempo_tpu.api import HTTPApi

    api = HTTPApi(app, multitenancy=True)
    (r,) = windowed(hunts(corpus, 10, variants=1), 0, 24)
    first = ask(api, r)
    staged = events("miss")
    hit, miss = (obs.prepare_memo.value(result=k) for k in ("hit", "miss"))
    second = ask(api, r)
    for a in (first, second):
        ok, why = op.check(r, a, corpus["manifest"])
        assert ok, why
    assert events("miss") - staged >= BLOCKS // 4 - 2
    assert obs.prepare_memo.value(result="miss") == miss
    assert obs.prepare_memo.value(result="hit") - hit == BLOCKS // 4
    batcher = app.reader_db.batcher
    assert settle(batcher) == 0
    kept = [h.query_memo for h in batcher.cache.snapshot()["host"].values()
            if h.query_memo is not None]
    assert kept and all("device_params" not in pre
                        for memo in kept for pre in memo.values())
    check_budget(batcher.cache)
    assert batcher.cache.snapshot()["hbm_bytes"] <= BUDGET


def test_a_restage_says_what_it_moved(corpus, app):
    """The instrumentation of a re-stage: `batcher.place` spans carry
    the bytes of the put, `batcher.stage` says `hbm_miss_host_hit`, the
    evicted bytes are counted, and the readers of the cell find them."""
    from chipbench.tests.test_span_layers import reader
    from chipbench.run import span_dicts
    from tempo_tpu.api import HTTPApi

    api = HTTPApi(app, multitenancy=True)
    (r,) = windowed(hunts(corpus, 8, variants=1), 0, 24)
    assert ask(api, r)["status"] == 200          # cold: from the store
    gone = obs.hbm_evicted_bytes.value()
    collector = tracing.CollectExporter()
    tracing.set_tracer(tracing.Tracer(tracing.SyncProcessor(collector)))
    try:
        assert ask(api, r)["status"] == 200      # again: from the host tier
    finally:
        tracing.set_tracer(None)
    assert settle(app.reader_db.batcher) == 0
    spans = span_dicts(collector.spans)
    places = [s for s in spans if s["name"] == "batcher.place"]
    searches = {s["span_id"] for s in spans if s["name"] == "batcher.Search"}
    assert places and all(s["parent_id"] in searches for s in places)
    assert all(s["attributes"]["bytes"] > 400_000
               and s["attributes"]["blocks"] == 4 for s in places)
    restaged = [s for s in spans if s["name"] == "batcher.stage"
                and s["attributes"]["cache"] == "hbm_miss_host_hit"]
    assert len(restaged) >= len(places) >= BLOCKS // 4 - 2
    assert obs.hbm_evicted_bytes.value() - gone >= sum(
        s["attributes"]["bytes"] for s in places) - 2 * 600_000
    run = {"spans": spans}
    assert reader("h2d_gbytes_per_s.evict")(run) > 0
    assert reader("restage_ms.evict")(run) > 0


def traced(api, request):
    """One request with a tracer installed: its answer and the groups
    its search took, in the order it took them, as (plan index, `pick`,
    `cache`) of its `batcher.stage` spans."""
    collector = tracing.CollectExporter()
    tracing.set_tracer(tracing.Tracer(tracing.SyncProcessor(collector)))
    try:
        answer = ask(api, request)
    finally:
        tracing.set_tracer(None)
    stages = sorted((s for s in collector.spans if s.name == "batcher.stage"),
                    key=lambda s: s.start_ns)
    return answer, [(s.attributes["group"], s.attributes["pick"],
                     s.attributes["cache"]) for s in stages]


def wait_until(condition, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not condition():
        assert time.monotonic() < deadline
        time.sleep(0.005)


def picks():
    return {k: obs.group_picks.value(pick=k)
            for k in ("resident", "joined", "staged")}


def plan_of(app):
    """The tenant's groups in plan order (a search has made the plan)."""
    (*_job_lists, groups), = app.reader_db._breq_jobs_cache.values()
    return groups, [tuple(j.key for j in g) for g in groups]


@pytest.mark.parametrize("quits", (False, True), ids=("to-the-end", "on-limit"))
def test_with_every_group_resident_the_walk_is_plan_order(corpus, roomy,
                                                          quits):
    """A budget that holds the tenant: rule 1 finds the first remaining
    group resident at every step, so a search takes its groups in plan
    order, as the walk over a list sorted once at its start did; one
    that fills its limit stops where that one stopped, a few groups in,
    and the look-ahead has nothing to stage."""
    from chipbench.ops import search as op
    from tempo_tpu.api import HTTPApi

    api = HTTPApi(roomy, multitenancy=True)
    if quits:
        (r,) = op.build({"op": "search", "variants": 1, "limit": 5,
                         "tags": {"http.method": {"fixed": "GET"}}},
                        corpus["manifest"], np.random.default_rng(7))
    else:
        (r,) = windowed(hunts(corpus, 12, variants=1), 0, 24)
    flush = windowed(hunts(corpus, 99, variants=1), 0, 24)[0]
    assert ask(api, flush)["status"] == 200     # stages all twelve, once
    staged, evicted = events("miss"), events("evict")
    before = picks()
    a, walk = traced(api, r)
    ok, why = op.check(r, a, corpus["manifest"])
    assert ok, why
    n = BLOCKS // 4
    assert [g for g, _p, _c in walk] == list(range(len(walk)))
    assert len(walk) <= roomy.reader_db.batcher.pipeline_depth + 1 \
        if quits else len(walk) == n
    assert {(p, c) for _g, p, c in walk} == {("resident", "hbm_hit")}
    assert picks() == dict(before, resident=before["resident"] + len(walk))
    assert (events("miss"), events("evict")) == (staged, evicted)
    assert settle(roomy.reader_db.batcher) == 0


def test_a_group_that_arrives_is_taken_before_one_to_be_staged(
        corpus, app, monkeypatch):
    """Groups 10 and 11 resident, a tenant-wide search under way, and
    group 9 put into HBM by someone else while it stages its first
    missing group: the search takes 9 next, as a resident, before any
    of the eight it would have to stage (a list fixed at its start had
    it last of twelve, after nine puts)."""
    from chipbench.ops import search as op
    from tempo_tpu.api import HTTPApi

    api = HTTPApi(app, multitenancy=True)
    (r,) = windowed(hunts(corpus, 13, variants=1), 0, 24)
    assert ask(api, r)["status"] == 200           # leaves 10 and 11
    batcher = app.reader_db.batcher
    assert settle(batcher) == 0
    groups, gkeys = plan_of(app)
    assert set(batcher.cache.snapshot()["entries"]) == set(gkeys[10:])
    real, guest = batcher.cache.staged, []

    def staged(group, pin=False, parent=None):
        """The search's first put brings group 9 with it, pinned."""
        entry = real(group, pin, parent)
        if not guest:
            guest.append(real(groups[9], True))
        return entry

    monkeypatch.setattr(batcher.cache, "staged", staged)
    missed = events("miss")
    a, walk = traced(api, r)
    ok, why = op.check(r, a, corpus["manifest"])
    assert ok, why
    with batcher.cache.group_lock:
        batcher.cache.unpin_locked(guest)
    order = [g for g, _p, _c in walk]
    assert sorted(order) == list(range(12))
    assert order == [10, 11, 0, 9] + list(range(1, 9))
    assert walk[3][1:] == ("resident", "hbm_hit")
    assert events("miss") - missed == 10      # 0 to 8, and the guest
    assert settle(batcher) == 0


def test_a_group_another_search_is_staging_is_joined(corpus, app,
                                                     monkeypatch):
    """Two searches of one hour that lies inside group 0, the second
    begun while the first's put is under way: the second waits on that
    put (`pick=joined`) and the group is staged once for the two."""
    from chipbench.ops import search as op
    from tempo_tpu.api import HTTPApi

    api = HTTPApi(app, multitenancy=True)
    first, second = windowed(hunts(corpus, 14, variants=2), 22.5, 1)
    flush = windowed(hunts(corpus, 99, variants=1), 0, 24)[0]
    assert ask(api, flush)["status"] == 200       # group 0 is long gone
    batcher = app.reader_db.batcher
    assert settle(batcher) == 0
    place, real = batcher.engine.place, batcher.cache.staged
    gate, waiting = threading.Event(), threading.Event()

    def staged(group, pin=False, parent=None):
        # the second search, about to wait
        if batcher.cache.snapshot()["staging"]:
            waiting.set()
        return real(group, pin, parent)

    def held(host):
        assert gate.wait(30)
        return place(host)

    monkeypatch.setattr(batcher.cache, "staged", staged)
    monkeypatch.setattr(batcher.engine, "place", held)
    before, missed = picks(), events("miss")
    visits = events("hit") + missed
    with ThreadPoolExecutor(2) as pool:
        one = pool.submit(ask, api, first)
        wait_until(lambda: batcher.cache.snapshot()["staging"])
        two = pool.submit(ask, api, second)
        assert waiting.wait(30)
        gate.set()
        answers = [one.result(), two.result()]
    for r, a in zip((first, second), answers):
        ok, why = op.check(r, a, corpus["manifest"])
        assert ok, why
    assert events("miss") - missed == 1
    assert events("hit") + events("miss") - visits == 2
    assert picks() == dict(before, staged=before["staged"] + 1,
                           joined=before["joined"] + 1)
    assert settle(batcher) == 0


def test_the_look_ahead_stages_only_what_nobody_holds_or_stages(
        corpus, app, monkeypatch):
    """Groups 10 and 11 resident and group 9's put held by another
    thread while a tenant-wide search runs: the look-ahead asks for
    nothing while the walk has a resident to take, then for one group at
    a time, in plan order, never for a resident one nor for group 9; the
    search takes group 9 last, by joining that put, and every group was
    staged once."""
    from chipbench.ops import search as op
    from tempo_tpu.api import HTTPApi

    api = HTTPApi(app, multitenancy=True)
    (r,) = windowed(hunts(corpus, 15, variants=1), 0, 24)
    assert ask(api, r)["status"] == 200           # leaves 10 and 11
    batcher = app.reader_db.batcher
    assert settle(batcher) == 0
    groups, gkeys = plan_of(app)
    cache = batcher.cache
    place, real, submit = (batcher.engine.place, cache.staged,
                           batcher._prefetcher.submit)
    gate, asked, guest = threading.Event(), [], []
    theirs = cache.snapshot()["host"][gkeys[9]]

    def held(host):
        if host is theirs:
            assert gate.wait(30)
        return place(host)

    def staged(group, pin=False, parent=None):
        if group is groups[9] and threading.current_thread().name != "other":
            gate.set()              # the search came to join: let it land
        return real(group, pin, parent)

    def ahead(fn, group, *args):
        key = tuple(j.key for j in group)
        pins = sum(p for _n, p, _m in cache.snapshot()["entries"].values())
        with cache.group_lock:
            asked.append((gkeys.index(key), cache.is_resident_locked(key),
                          cache.is_staging_locked(key), pins))
        return submit(fn, group, *args)

    monkeypatch.setattr(batcher.engine, "place", held)
    monkeypatch.setattr(cache, "staged", staged)
    monkeypatch.setattr(batcher._prefetcher, "submit", ahead)
    other = threading.Thread(
        target=lambda: guest.append(real(groups[9], True)), name="other")
    other.start()
    wait_until(lambda: cache.snapshot()["staging"])
    missed, before = events("miss"), picks()
    a, walk = traced(api, r)
    other.join(30)
    assert not other.is_alive()
    with cache.group_lock:
        cache.unpin_locked(guest)
    ok, why = op.check(r, a, corpus["manifest"])
    assert ok, why
    assert [g for g, _p, _c in walk] == [10, 11] + list(range(10))
    assert [p for _g, p, _c in walk] == (["resident"] * 2 + ["staged"] * 9
                                         + ["joined"])
    assert [g for g, *_ in asked] == list(range(9))
    assert not any(resident or staging for _g, resident, staging, _ in asked)
    # the first is asked for when the walk has taken its last resident
    assert asked[0][3] == 2
    assert events("miss") - missed == 10
    assert {k: v - before[k] for k, v in picks().items()} == {
        "resident": 2, "staged": 9, "joined": 1}
    assert settle(batcher) == 0


def test_a_look_ahead_that_never_ran_costs_nothing(corpus, app):
    """The look-ahead's threads all busy: what the search asks for never
    starts, the walk reaches each group first, takes the request back
    and stages the group itself, once; nothing stays pinned."""
    from chipbench.ops import search as op
    from tempo_tpu.api import HTTPApi

    api = HTTPApi(app, multitenancy=True)
    (r,) = windowed(hunts(corpus, 16, variants=1), 0, 24)
    assert ask(api, r)["status"] == 200
    batcher = app.reader_db.batcher
    assert settle(batcher) == 0
    gate = threading.Event()
    busy = [batcher._prefetcher.submit(gate.wait, 30) for _ in range(4)]
    try:
        missed, before = events("miss"), picks()
        a, walk = traced(api, r)
    finally:
        gate.set()
    assert all(f.result() for f in busy)
    ok, why = op.check(r, a, corpus["manifest"])
    assert ok, why
    assert [g for g, _p, _c in walk] == [10, 11] + list(range(10))
    assert [p for _g, p, _c in walk] == ["resident"] * 2 + ["staged"] * 10
    assert events("miss") - missed == 10
    assert settle(batcher) == 0


@pytest.mark.parametrize("callers", (1, 6))
def test_the_picks_sum_to_the_group_visits(corpus, app, callers):
    """`tempo_search_group_picks_total` moves once a group visit: over
    searches that run to their end its three series sum to the cache's
    hits and misses, each `batcher.stage` span carries the series it
    moved, and `/metrics` shows it."""
    from tempo_tpu.api import HTTPApi

    api = HTTPApi(app, multitenancy=True)
    requests = windowed(hunts(corpus, 17, variants=callers), 0, 24)
    before, visits = picks(), events("hit") + events("miss")
    if callers == 1:
        a, walk = traced(api, requests[0])
        assert a["status"] == 200 and len(walk) == BLOCKS // 4
        for k in before:
            assert picks()[k] - before[k] == sum(p == k for _g, p, _c in walk)
    else:
        with ThreadPoolExecutor(callers) as pool:
            assert all(a["status"] == 200 for a in pool.map(
                lambda r: ask(api, r), requests))
    assert settle(app.reader_db.batcher) == 0
    moved = sum(picks().values()) - sum(before.values())
    assert moved == callers * (BLOCKS // 4)
    assert moved == events("hit") + events("miss") - visits
    code, text = api.handle("GET", "/metrics", {}, {})
    assert code == 200
    for k in before:
        assert f'tempo_search_group_picks_total{{pick="{k}"}}' in str(text)


def _decoded(seed, geometry):
    """A block as the store gives it back: every array a view of one
    decoded buffer (`ColumnarPages.from_bytes`)."""
    from tempo_tpu.search.columnar import ColumnarPages
    from tempo_tpu.search.data import SearchData

    rng = random.Random(seed)
    entries = []
    for i in range(40):
        sd = SearchData(trace_id=bytes([seed, i]) * 8)
        sd.start_s = 1_600_000_000 + i
        sd.end_s = sd.start_s + rng.randint(0, 10)
        sd.dur_ms = rng.randint(1, 30_000)
        sd.root_service = rng.choice(["frontend", "checkout", "cart"])
        sd.root_name = "GET /"
        sd.kvs = {"service.name": {sd.root_service},
                  "region": {rng.choice(["us-east-1", "eu-west-1"])}}
        entries.append(sd)
    return ColumnarPages.from_bytes(
        ColumnarPages.build(entries, geometry).to_bytes())


@pytest.mark.parametrize("layout", ("stacked", "one_block", "packed"))
def test_a_host_tier_entry_holds_each_column_once(layout, monkeypatch):
    """Stacked in the plain layout, the entry's blocks read the stacked
    columns from the stacked copy and own the rest (trace ids, root
    names), so no block pins its decoded buffer beside the copy: the
    entry is charged the copy and what the blocks own, and the values
    are the source's. One block alone is served as views of itself and a
    packed layout has other values: both keep their blocks as they
    came."""
    from tempo_tpu.search import packing
    from tempo_tpu.search.columnar import PageGeometry
    from tempo_tpu.search.multiblock import _STACKED, stack_host

    if layout == "packed":
        monkeypatch.setattr(packing.PACKING, "enabled", True)
    source = [_decoded(s, PageGeometry(8, 8))
              for s in range(1 if layout == "one_block" else 3)]
    host = stack_host(source)
    if layout != "stacked":
        if layout == "packed":
            assert host.widths is not None
        assert host.aliased_nbytes == 0
        assert all(b is s for b, s in zip(host.blocks, source))
        return
    own = 0
    for b, s, off in zip(host.blocks, source, host.page_offset):
        assert b is not s and b.val_dict is s.val_dict
        assert b._dict_section_sha == s._dict_section_sha   # memos come along
        for name, _ in s._ARRAYS:
            got, want = getattr(b, name), getattr(s, name)
            assert np.array_equal(got, want), name
            if name in _STACKED:
                assert np.shares_memory(got, host.cat[name]), name
            else:
                assert got.base is None and not np.shares_memory(got, want)
                own += got.nbytes
    assert host.aliased_nbytes == sum(
        getattr(b, n).nbytes for b in host.blocks for n in _STACKED)
    assert host.nbytes == host.cat_nbytes + own
    assert host.nbytes < sum(s.nbytes for s in source)


# ---- PR 47: the budget's one rule. `search/group_cache.py` writes the
# running totals in three functions; whatever moves bytes, the totals are
# the sums over the resident entries ----

GROUP_BYTES, DICT_BYTES, SPAN_BYTES = 1000, 100, 40


class _Arr:
    def __init__(self, nbytes):
        self.nbytes = nbytes


class _Masks:
    """Stands in for `pipeline.MASK_BYTES`: this cache's masks alone."""

    def __init__(self):
        self.memo = 0

    def add(self, holder, nbytes):
        assert holder == "memo"
        self.memo += nbytes


class _Host:
    def __init__(self):
        self.nbytes = self.logical_nbytes = 3 * GROUP_BYTES
        self.query_memo = None
        self.query_cache = OrderedDict()


class _Batch:
    """What `engine.place` returns, as far as the cache reads it."""
    nbytes = device_nbytes = GROUP_BYTES + DICT_BYTES + SPAN_BYTES
    logical_nbytes = 2 * GROUP_BYTES
    blocks = ()

    def __init__(self):
        self.staged_dicts = {"fp": _Arr(DICT_BYTES)}
        self.span_device = {"span_trace": np.zeros(SPAN_BYTES, np.int8)}


class _Engine:
    n_shards = 2

    def stage_host(self, pages):
        return _Host()

    def place(self, host):
        return _Batch()


def _group(i):
    return [ScanJob(key=(f"blk-{i}", 0, 1), pages_fn=lambda: None, header={},
                    n_pages=1, n_entries=1, geometry=(1, 1))]


def _pre(mask=0):
    return {"val_hits": _Arr(mask) if mask else None}


@pytest.fixture
def small(monkeypatch):
    """A GroupCache over a stand-in engine, with a budget of three
    groups, two of them staged; its masks counted apart from the
    process's."""
    from tempo_tpu.search import group_cache

    masks = _Masks()
    monkeypatch.setattr(group_cache, "MASK_BYTES", masks)
    cache = group_cache.GroupCache(_Engine(), 3 * _Batch.nbytes + 500,
                                   1 << 30, 2)
    entries = [cache.staged(_group(i)) for i in range(2)]
    return cache, entries, masks


def _ev_stage(cache, entries):
    cache.staged(_group(2))
    return 3


def _ev_restage_over_a_previous_entry(cache, entries):
    from tempo_tpu.search.group_cache import _CachedBatch

    gkey = next(iter(cache.snapshot()["entries"]))
    cache.memo_put(gkey, entries[0], "p", _pre(mask=64))  # leaves with it
    new = _CachedBatch(batch=_Batch(), nbytes=_Batch.nbytes + 7, logical=5)
    with cache.group_lock:
        cache._insert_locked(gkey, new)
    assert cache.resident(gkey) is new
    return 2


def _ev_memo_params(cache, entries):
    gkey = next(iter(cache.snapshot()["entries"]))
    pre = _pre()
    cache.memo_params(gkey, entries[0], pre, (_Arr(30), _Arr(12)))
    cache.memo_params(gkey, entries[0], pre, (_Arr(999),))   # kept: once
    # replicated on the mesh: every device holds the whole of each
    assert pre["device_params_bytes"] == 84
    assert entries[0].nbytes == _Batch.nbytes + 84
    return 2


def _ev_memo_put_with_a_mask(cache, entries):
    gkey = next(iter(cache.snapshot()["entries"]))
    cache.memo_put(gkey, entries[0], "p", _pre(mask=64))
    assert cache.memo_get(entries[0], "p")["mask_bytes"] == 64
    assert cache.memo_get(entries[0], "q") is None
    assert entries[0].mask_bytes == 64
    return 2


def _ev_the_memos_lru_pop(cache, entries):
    from tempo_tpu.search.group_cache import _QUERY_CACHE_MAX

    gkey = next(iter(cache.snapshot()["entries"]))
    first = _pre(mask=10)
    cache.memo_put(gkey, entries[0], 0, first)
    cache.memo_params(gkey, entries[0], first, (_Arr(3),))
    for sig in range(1, _QUERY_CACHE_MAX + 1):
        cache.memo_put(gkey, entries[0], sig, _pre(mask=1))
    assert cache.memo_get(entries[0], 0) is None       # popped, refunded
    assert entries[0].mask_bytes == _QUERY_CACHE_MAX
    assert entries[0].nbytes == _Batch.nbytes + _QUERY_CACHE_MAX
    return 2


def _ev_the_host_routes_memo_charges_nothing(cache, entries):
    from tempo_tpu.search.group_cache import _QUERY_CACHE_MAX

    gkey, host = next(iter(cache.snapshot()["host"].items()))
    for sig in range(_QUERY_CACHE_MAX + 2):
        cache.memo_put(gkey, host, sig, _pre())
    assert list(host.query_cache) == list(range(2, _QUERY_CACHE_MAX + 2))
    assert cache.memo_get(host, 2)["mask_bytes"] == 0
    assert next(reversed(host.query_cache)) == 2           # touched
    assert host.nbytes == 3 * GROUP_BYTES
    assert cache.snapshot()["host_bytes"] == 2 * host.nbytes
    return 2


def _ev_lru_eviction(cache, entries):
    for i in range(2, 5):
        cache.staged(_group(i))
    assert [k[0][0] for k in cache.snapshot()["entries"]] == [
        "blk-2", "blk-3", "blk-4"]
    return 3


def _ev_a_charge_that_pushes_over_budget_evicts(cache, entries):
    cache.staged(_group(2))
    gkey = tuple(j.key for j in _group(2))
    cache.memo_put(gkey, cache.resident(gkey), "p", _pre(mask=600))
    return 2


def _ev_a_search_charges_an_entry_evicted_meanwhile(cache, entries):
    gkey = next(iter(cache.snapshot()["entries"]))
    with cache.group_lock:
        cache._drop_hbm_locked(gkey)
    before = cache.snapshot()["hbm_bytes"]
    pre = _pre(mask=64)
    cache.memo_put(gkey, entries[0], "p", pre)         # the guard: the
    cache.memo_params(gkey, entries[0], pre, (_Arr(8),))   # entry alone
    assert cache.snapshot()["hbm_bytes"] == before
    assert entries[0].nbytes == _Batch.nbytes + 64 + 16
    assert entries[0].mask_bytes == 64
    return 1


def _ev_a_key_column_is_charged_and_leaves_with_its_group(cache, entries):
    """The ?agg= key column (`GroupCache.agg_staged` charges it so): in
    the total while its group is resident, gone with the group, and an
    entry evicted meanwhile carries it alone."""
    first, second = cache.snapshot()["entries"]
    before = cache.snapshot()["hbm_bytes"]
    with cache.group_lock:
        cache.charge_locked(first, entries[0], agg=96)
        assert cache._agg_total == 96
    assert cache.snapshot()["hbm_bytes"] == before + 96
    with cache.group_lock:
        cache._drop_hbm_locked(first)
        assert cache._agg_total == 0
        cache.charge_locked(first, entries[0], agg=96)   # evicted: no total
        assert cache._agg_total == 0
    assert entries[0].agg_bytes == 192
    assert cache.snapshot()["hbm_bytes"] == before - _Batch.nbytes
    return 1


def _ev_invalidate(cache, entries):
    cache.memo_put(next(iter(cache.snapshot()["entries"])), entries[0], "p",
                   _pre(mask=64))
    cache.invalidate({"blk-1"})
    assert list(cache.snapshot()["host"]) == list(cache.snapshot()["entries"])
    assert cache.snapshot()["host_bytes"] == 3 * GROUP_BYTES
    return 1


def _ev_a_rebalance_drop(cache, entries):
    from tempo_tpu.search import ownership

    ownership.configure(enabled=True, members="m0,m1", self_id="spectator",
                        groups=32)
    try:
        assert cache.rebalance_ownership() == {"hbm_dropped": 2,
                                               "hbm_deferred": 0}
    finally:
        ownership.OWNERSHIP.reset()
    return 0


def _ev_a_deferred_drop_at_unpin(cache, entries):
    from tempo_tpu.search import ownership

    held = cache.staged(_group(0), pin=True)
    assert held is entries[0] and held.pins == 1
    ownership.configure(enabled=True, members="m0,m1", self_id="spectator",
                        groups=32)
    try:
        assert cache.rebalance_ownership() == {"hbm_dropped": 1,
                                               "hbm_deferred": 1}
        assert check_budget(cache) == {
            tuple(j.key for j in _group(0)): (_Batch.nbytes, 1, 0)}
        with cache.group_lock:
            cache.unpin_locked((held,))
    finally:
        ownership.OWNERSHIP.reset()
    return 0


BYTE_EVENTS = [_ev_stage, _ev_restage_over_a_previous_entry, _ev_memo_params,
               _ev_memo_put_with_a_mask, _ev_the_memos_lru_pop,
               _ev_the_host_routes_memo_charges_nothing,
               _ev_lru_eviction, _ev_a_charge_that_pushes_over_budget_evicts,
               _ev_a_search_charges_an_entry_evicted_meanwhile,
               _ev_a_key_column_is_charged_and_leaves_with_its_group,
               _ev_invalidate, _ev_a_rebalance_drop,
               _ev_a_deferred_drop_at_unpin]


@pytest.mark.parametrize("event", BYTE_EVENTS,
                         ids=lambda f: f.__name__[4:].replace("_", "-"))
def test_whatever_moves_bytes_the_totals_are_the_residents_sums(small, event):
    """After every event that moves bytes into or out of the HBM budget
    the running totals (bytes, logical bytes, dictionaries, span
    columns, the memo's masks) are the sums over the entries that are
    resident."""
    cache, entries, masks = small
    assert len(check_budget(cache)) == 2
    left = event(cache, entries)
    now = check_budget(cache)
    assert len(now) == left
    assert masks.memo == sum(m for _n, _p, m in now.values())
    assert all(m <= n for n, _p, m in now.values())
    assert cache.snapshot()["hbm_bytes"] <= cache.cache_bytes


TOTALS = {"_cache_total", "_cache_logical", "_probe_dict_total",
          "_span_total", "_agg_total", "_host_total", "_host_logical"}
WRITERS = {"__init__", "_insert_locked", "_remove_locked", "charge_locked",
           "_insert_host_locked", "_remove_host_locked", "charge_cpu_copies"}


def _writes(tree):
    """(function, what) of every assignment to a running total and
    every `MASK_BYTES.add("memo", ...)` in a module."""
    import ast

    found = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for node in ast.walk(fn):
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            for t in targets:
                for leaf in ast.walk(t):
                    if isinstance(leaf, ast.Attribute) and leaf.attr in TOTALS:
                        found.append((fn.name, leaf.attr))
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "add"
                    and getattr(node.func.value, "id", "") == "MASK_BYTES"
                    and node.args
                    and getattr(node.args[0], "value", None) == "memo"):
                found.append((fn.name, "MASK_BYTES"))
    return found


def test_the_totals_are_written_in_the_cache_module_alone():
    """No file under tempo_tpu/ but search/group_cache.py assigns to a
    running total or moves the memo's mask bytes, that module only in
    its insert / remove / charge functions, and the search loop names
    no field of the cache."""
    import ast
    import pathlib

    import tempo_tpu

    root = pathlib.Path(tempo_tpu.__file__).parent
    seen = 0
    for path in sorted(root.rglob("*.py")):
        tree = ast.parse(path.read_text())
        writes = _writes(tree)
        if path.name == "group_cache.py":
            seen += 1
            assert writes and {fn for fn, _ in writes} <= WRITERS, writes
            assert {fn for fn, what in writes
                    if what == "MASK_BYTES"} <= WRITERS - {"__init__"}
            assert TOTALS <= {what for _fn, what in writes}
        else:
            assert not writes, (str(path), writes)
        if path.name == "batcher.py":
            seen += 1
            loop = next(n for n in ast.walk(tree)
                        if isinstance(n, ast.FunctionDef)
                        and n.name == "_search_impl")
            named = {n.attr for n in ast.walk(loop)
                     if isinstance(n, ast.Attribute)}
            assert not named & {"_cache", "_host_cache", "_staging",
                                "_evict_hbm_locked", "_lock"} | TOTALS & named
    assert seen == 2
