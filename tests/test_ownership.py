"""Owner-routed HBM (ISSUE 11): the ownership map's placement contract,
the batcher's non-owner host route + rebalance eviction semantics, the
frontend's owner routing, and the disabled-path noop.

Placement cross-checks (the dedup-consistent-hashing satellite): the
shared jump hash and the ring-derived owner table must both be STABLE
under member add/remove — adding a member moves only the groups it
takes, removing it restores the previous placement exactly.

Byte-identity canon mirrors tests/test_faults.py: device_seconds is
measured wall time and the device/host byte split moves with placement
BY DESIGN, so identity is asserted on the canonical response."""

from __future__ import annotations

import threading

import pytest

from tempo_tpu import robustness, tempopb
from tempo_tpu.observability import metrics as obs
from tempo_tpu.search import ownership
from tempo_tpu.search.ownership import OWNERSHIP, OwnershipMap

from conftest import check_budget
from test_faults import _canon, _mkdb, _req


@pytest.fixture(autouse=True)
def _clean_ownership():
    """Every test starts (and leaves) with the layer factory-reset —
    the map is process-wide like the breaker/profiler."""
    OWNERSHIP.reset()
    yield
    OWNERSHIP.reset()


# ------------------------------------------------------------ placement


def test_shared_jump_hash_one_implementation():
    """The netcache server selector and the ownership map consume ONE
    jump-hash helper (utils.hashing) — the dedup satellite's contract."""
    from tempo_tpu.backend import netcache
    from tempo_tpu.utils import hashing

    assert netcache.jump_hash is hashing.jump_hash


def test_placement_spreads_and_is_deterministic():
    a = OwnershipMap(n_groups=64)
    a.set_members(["h0", "h1", "h2"])
    b = OwnershipMap(n_groups=64)
    b.set_members(["h0", "h1", "h2"])
    # identical tables from the same member list on two "processes"
    assert a._owners == b._owners
    counts: dict = {}
    for o in a._owners:
        counts[o] = counts.get(o, 0) + 1
    assert set(counts) == {"h0", "h1", "h2"}
    # roughly even: nobody owns more than 60% of the groups
    assert max(counts.values()) <= 64 * 0.6


def test_placement_stable_under_member_add_remove():
    """Adding a member moves ONLY the groups it takes; removing it
    restores the previous placement exactly — the consistent-hash
    stability cross-check for the ring-derived owner table."""
    m = OwnershipMap(n_groups=64)
    m.set_members(["h0", "h1", "h2"])
    before = m._owners
    gen1 = m.generation
    moved = m.set_members(["h0", "h1", "h2", "h3"])
    assert m.generation == gen1 + 1
    after = m._owners
    changed = [g for g in range(64) if before[g] != after[g]]
    assert moved == len(changed)
    assert 0 < moved < 64  # some movement, never a full reshuffle
    # every moved group went TO the new member, none between old members
    assert all(after[g] == "h3" for g in changed)
    moved_back = m.set_members(["h0", "h1", "h2"])
    assert moved_back == moved
    assert m._owners == before


def test_set_members_idempotent_no_generation_churn():
    m = OwnershipMap()
    m.set_members(["a", "b"], self_id="a")
    gen = m.generation
    assert m.set_members(["a", "b"]) == 0
    assert m.generation == gen  # repeated configure() must not churn


def test_jump_hash_minimal_movement_groups():
    """The block -> placement-group step inherits jump-hash movement:
    growing the group count only moves blocks INTO new groups."""
    from tempo_tpu.utils.hashing import fnv1a_64, jump_hash

    keys = [fnv1a_64(f"block-{i}".encode()) for i in range(2000)]
    before = {k: jump_hash(k, 32) for k in keys}
    after = {k: jump_hash(k, 48) for k in keys}
    moved = [k for k in keys if before[k] != after[k]]
    assert all(after[k] >= 32 for k in moved)
    assert len(moved) < len(keys) * 0.5


def test_disabled_is_permissive_and_cheap():
    assert OWNERSHIP.enabled is False
    assert OWNERSHIP.owns_group((("blk", 0, 4),)) is True
    assert OWNERSHIP.owns_block("blk") is True
    assert OWNERSHIP.owner_index("blk") is None


def test_configure_auto_members_from_multihost_env(monkeypatch):
    monkeypatch.setenv("TEMPO_NUM_PROCESSES", "4")
    monkeypatch.setenv("TEMPO_PROCESS_ID", "2")
    ownership.configure(enabled=True)
    assert OWNERSHIP.members == tuple(f"host-{i}" for i in range(4))
    assert OWNERSHIP.self_id == "host-2"


def test_configure_groups_rebuilds_table():
    ownership.configure(enabled=True, members="a,b", groups=16)
    assert OWNERSHIP.n_groups == 16
    assert len(OWNERSHIP._owners) == 16


# ------------------------------------------------- serving-path routing


def test_byte_identity_on_off_all_engine_paths(tmp_path):
    """Ownership on vs off is byte-identical on the one-block,
    batched, and coalesced paths — whether this member owns everything,
    half, or nothing (a pure non-owner serves 100% host-routed)."""
    db = _mkdb(tmp_path, n_blocks=6, search_max_batch_pages=8,
               search_coalesce_window_s=0.02, search_coalesce_max_queries=4)
    req = _req(limit=10_000)
    base = _canon(db.search("t", req).response())

    for self_id in ("m0", "m1", "spectator"):  # spectator owns nothing
        ownership.configure(enabled=True, members="m0,m1",
                            self_id=self_id, groups=32)
        assert _canon(db.search("t", req).response()) == base, self_id
        OWNERSHIP.reset()

    # one block by its meta (TempoDB.search_meta: a one-block batch)
    from tempo_tpu.search import SearchResults

    meta = db.blocklist.metas("t")[0]
    sreq = _req(limit=10_000)

    def one_block():
        results = SearchResults.for_request(sreq)
        db.search_meta(meta, sreq, results)
        return results.response().SerializeToString()

    single_base = one_block()
    ownership.configure(enabled=True, members="m0,m1",
                        self_id="spectator", groups=32)
    before = obs.scan_dispatches.value(mode="host_fallback")
    assert one_block() == single_base
    assert obs.scan_dispatches.value(mode="host_fallback") > before

    # coalesced: concurrent same-tenant searches under ownership fuse /
    # host-route per group and still match serial
    reqs = []
    for i in range(4):
        r = tempopb.SearchRequest()
        r.tags["service.name"] = f"svc-{i:02d}"
        r.limit = 10_000
        reqs.append(r)
    OWNERSHIP.reset()
    serial = [_canon(db.search("t", r).response()) for r in reqs]
    ownership.configure(enabled=True, members="m0,m1", self_id="m0",
                        groups=32)
    got = [None] * 4
    barrier = threading.Barrier(4)

    def worker(i):
        barrier.wait()
        got[i] = _canon(db.search("t", reqs[i]).response())

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert got == serial


@pytest.mark.skipif("len(__import__('jax').devices()) < 2")
def test_byte_identity_mesh_path(tmp_path):
    """Ownership on/off identity with the batch sharded over the device
    mesh (the dist kernel serving path)."""
    db = _mkdb(tmp_path, n_blocks=4, auto_mesh=True)
    req = _req(limit=10_000)
    base = _canon(db.search("t", req).response())
    ownership.configure(enabled=True, members="m0,m1", self_id="m1",
                        groups=32)
    assert _canon(db.search("t", req).response()) == base


def test_non_owner_stages_nothing(tmp_path):
    """A pure non-owner serves every group through the host route and
    its HBM cache stays EMPTY — the no-duplicate-copy contract."""
    db = _mkdb(tmp_path, n_blocks=4, search_max_batch_pages=8)
    req = _req(limit=10_000)
    ownership.configure(enabled=True, members="m0,m1",
                        self_id="spectator", groups=32)
    before_non = obs.hbm_owner_routed.value(route="non_owner_host")
    r = db.search("t", req).response()
    assert r.metrics.inspected_blocks == 4
    assert not db.batcher.cache.snapshot()["entries"]  # nothing staged to HBM
    assert db.batcher.cache.snapshot()["host"]  # served from the host tier
    assert obs.hbm_owner_routed.value(route="non_owner_host") > before_non


def test_prewarm_skips_non_owned_groups(tmp_path):
    db = _mkdb(tmp_path, n_blocks=4, search_max_batch_pages=8)
    jobs = [db._scan_job(m) for m in db.blocklist.metas("t")]
    groups = db.batcher.plan(jobs)
    assert len(groups) >= 2
    ownership.configure(enabled=True, members="m0,m1",
                        self_id="spectator", groups=32)
    assert db.batcher.prewarm(groups, warm_compile=False) == 0
    assert not db.batcher.cache.snapshot()["entries"]
    OWNERSHIP.self_id = "m0"
    owned = [g for g in groups
             if OWNERSHIP.owns_group(tuple(j.key for j in g))]
    staged = db.batcher.prewarm(groups, warm_compile=False)
    assert staged == len(owned)
    assert len(db.batcher.cache.snapshot()["entries"]) == len(owned)


# ------------------------------------------- rebalance + eviction shape


def _deferred(cache) -> set:
    """Anchor blocks of the resident groups a deferred eviction waits
    on, as /debug/ownership shows them."""
    return {r["anchor_block"] for r in cache.ownership_residency()
            if r["deferred_evict"]}


def test_rebalance_drops_unowned_defers_pinned(tmp_path):
    db = _mkdb(tmp_path, n_blocks=4, search_max_batch_pages=8)
    req = _req(limit=10_000)
    db.search("t", req)  # stage everything (ownership off)
    b = db.batcher.cache
    keys = list(b.snapshot()["entries"])
    assert keys
    ownership.configure(enabled=True, members="m0,m1",
                        self_id="spectator", groups=32)
    # pin one batch (an in-flight search), leave the rest unpinned
    pinned_key = keys[0]
    held = b.resident(pinned_key)
    with b.group_lock:
        held.pins += 1
    out = b.rebalance_ownership()
    assert out["hbm_dropped"] == len(keys) - 1
    assert out["hbm_deferred"] == 1
    assert set(b.snapshot()["entries"]) == {pinned_key}
    assert b.snapshot()["hbm_bytes"] == held.nbytes
    assert _deferred(b) == {str(pinned_key[0][0])}
    # unpin: the deferred eviction runs exactly once
    with b.group_lock:
        held.pins -= 1
        b._run_deferred_evictions_locked()
    assert not b.snapshot()["entries"] and b.snapshot()["hbm_bytes"] == 0
    # idempotent: a second sweep cannot double-subtract (the
    # negative-bytes regression shape)
    with b.group_lock:
        b._run_deferred_evictions_locked()
        b._evict_hbm_locked()
    assert b.snapshot()["hbm_bytes"] == 0


def test_deferred_eviction_stale_marker_never_double_evicts(tmp_path):
    """An ownership deferral and an LRU eviction targeting the SAME
    batch must evict once: after the LRU (or a re-stage) got there
    first, the stale marker is discarded by entry identity — the budget
    never goes negative and a fresh batch under the same key
    survives."""
    db = _mkdb(tmp_path, n_blocks=4, search_max_batch_pages=8)
    req = _req(limit=10_000)
    db.search("t", req)
    b = db.batcher.cache
    ownership.configure(enabled=True, members="m0,m1",
                        self_id="spectator", groups=32)
    gkey = next(iter(b.snapshot()["entries"]))
    entry = b.resident(gkey)
    with b.group_lock:
        entry.pins += 1
    b.rebalance_ownership()
    assert _deferred(b) == {str(gkey[0][0])}
    # unpin, then an LRU eviction claims the batch BEFORE the sweep
    with b.group_lock:
        entry.pins -= 1
        b._drop_hbm_locked(gkey)
    total_after_lru = b.snapshot()["hbm_bytes"]
    with b.group_lock:
        b._run_deferred_evictions_locked()  # stale marker: must no-op
    assert b.snapshot()["hbm_bytes"] == total_after_lru >= 0
    # a fresh batch re-staged under the same key is NOT a victim of the
    # old marker either
    OWNERSHIP.reset()
    db.search("t", req)  # re-stages (ownership off)
    with b.group_lock:
        b._run_deferred_evictions_locked()
    assert b.resident(gkey) not in (None, entry) and not _deferred(b)
    check_budget(b)


def test_tempodb_rebalance_prestages_new_groups(tmp_path):
    db = _mkdb(tmp_path, n_blocks=4, search_max_batch_pages=8)
    req = _req(limit=10_000)
    ownership.configure(enabled=True, members="m0,m1", self_id="m0",
                        groups=32)
    db.search("t", req)  # warm the jobs cache + stage owned groups
    owned_before = len(db.batcher.cache.snapshot()["entries"])
    # m1 leaves: m0 now owns everything; prestage runs in background
    out = db.rebalance_ownership(["m0"], self_id="m0", prestage=True)
    assert out["generation"] == OWNERSHIP.generation
    assert out["moved_groups"] > 0
    deadline = __import__("time").time() + 30
    jobs = [db._scan_job(m) for m in db.blocklist.metas("t")]
    n_groups = len(db.batcher.plan(jobs))
    while __import__("time").time() < deadline:
        if len(db.batcher.cache.snapshot()["entries"]) >= n_groups:
            break
        __import__("time").sleep(0.05)
    assert len(db.batcher.cache.snapshot()["entries"]) >= max(owned_before,
                                                              n_groups)
    assert _canon(db.search("t", req).response())  # still serves


# ------------------------------------------------------- frontend layer


class _RecordingQuerier:
    """Wraps a real Querier; records routed block ids and can play a
    dead owner (raise on search_blocks)."""

    def __init__(self, inner):
        self.inner = inner
        self.db = inner.db
        self.die = False
        self.block_batches: list = []

    def search_recent(self, tenant, req):
        return self.inner.search_recent(tenant, req)

    def search_blocks(self, breq):
        self.block_batches.append([j.block_id for j in breq.jobs])
        if self.die:
            raise RuntimeError("owner died")
        return self.inner.search_blocks(breq)


def _frontend(tmp_path, n_blocks=6):
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend
    from tempo_tpu.modules.querier import Querier
    from tempo_tpu.modules.ring import Ring

    db = _mkdb(tmp_path, n_blocks=n_blocks, search_max_batch_pages=8)
    q = Querier(db, Ring(), {})
    proxies = [_RecordingQuerier(q), _RecordingQuerier(q)]
    fe = QueryFrontend(proxies, FrontendConfig(retries=3))
    return db, proxies, fe


def test_frontend_routes_batches_to_owner(tmp_path):
    db, proxies, fe = _frontend(tmp_path)
    req = _req(limit=10_000)
    base = _canon(fe.search("t", req))
    ownership.configure(enabled=True, members="m0,m1", self_id="m0",
                        groups=32)
    for p in proxies:
        p.block_batches.clear()
    got = _canon(fe.search("t", req))
    assert got == base
    # every batch a querier received is owned (first attempt) by the
    # member that maps to it — owner-pure batches, owner-routed
    routed = 0
    for qi, p in enumerate(proxies):
        for batch in p.block_batches:
            owners = {OWNERSHIP.owner_index(b) for b in batch}
            assert len(owners) == 1, "batch mixes owners"
            assert owners == {qi}
            routed += 1
    assert routed >= 1
    # each member that owns any block served at least one batch
    owners_present = {OWNERSHIP.owner_index(m.block_id)
                      for m in db.blocklist.metas("t")}
    for qi in owners_present:
        assert proxies[qi].block_batches, f"owner {qi} never routed to"


def test_frontend_owner_death_degrades_to_peer(tmp_path):
    """Owner death: the first attempt fails, the retry lands on the
    round-robin pool and the answer stays byte-identical — the peer is
    a non-owner, so it serves the host route, never a duplicate
    stage."""
    db, proxies, fe = _frontend(tmp_path)
    req = _req(limit=10_000)
    base = _canon(fe.search("t", req))
    ownership.configure(enabled=True, members="m0,m1", self_id="m0",
                        groups=32)
    proxies[0].die = True  # member 0's querier is gone
    got = _canon(fe.search("t", req))
    assert got == base


def test_frontend_pool_resize_mid_flight_keeps_plan_mapping(tmp_path):
    """Regression (satellite): the batch plan carries the pool width it
    was computed against, so a querier joining the pool BETWEEN
    planning and dispatch cannot silently remap every owner — the
    in-flight batch lands on the plan-mapped querier, and the new pool
    member only receives freshly-planned work."""
    db, proxies, fe = _frontend(tmp_path)
    ownership.configure(enabled=True, members="m0,m1,m2", self_id="m0",
                        groups=32)
    req = _req(limit=10_000)
    batches = fe._search_batches("t")
    assert all(b[3] == 2 for b in batches)  # planned against 2 queriers
    payload, template, owner, width = next(
        b for b in batches if b[2] is not None)
    breq = tempopb.SearchBlocksRequest()
    breq.CopyFrom(template)
    breq.search_req.CopyFrom(req)
    breq.tenant_id = "t"
    # the pool grows mid-flight
    q3 = _RecordingQuerier(proxies[0].inner)
    fe.queriers.append(q3)
    fe._dispatch_batch(breq, owner, width, payload[0][0].block_id)
    # plan-width mapping: owner % 2 — the live-pool indexing this
    # replaces would have sent owner-2 batches to the NEW querier
    assert not q3.block_batches
    assert proxies[owner % width].block_batches


def test_frontend_batch_plan_rekeys_on_generation(tmp_path):
    db, proxies, fe = _frontend(tmp_path)
    ownership.configure(enabled=True, members="m0,m1", groups=32)
    b1 = fe._search_batches("t")
    assert fe._search_batches("t") is b1  # memoized within a generation
    OWNERSHIP.set_members(["m0", "m1", "m2"])
    b2 = fe._search_batches("t")
    assert b2 is not b1  # a rebalance invalidates the routing plan


# ------------------------------------------------------------- surfaces


def test_debug_ownership_snapshot_shape(tmp_path):
    from tempo_tpu.api.http import HTTPApi

    db = _mkdb(tmp_path, n_blocks=2)
    db.search("t", _req())

    class _App:
        reader_db = db

    ownership.configure(enabled=True, members="m0,m1", self_id="m0",
                        groups=16)
    api = HTTPApi(_App(), debug_endpoints=True)
    code, body = api._debug_ownership_route({})
    assert code == 200
    import json

    doc = json.loads(json.dumps(body))
    assert doc["enabled"] is True
    assert doc["members"] == ["m0", "m1"]
    assert len(doc["owners"]) == 16
    assert isinstance(doc["residency"], list) and doc["residency"]
    row = doc["residency"][0]
    assert {"anchor_block", "placement_group", "owner", "owned",
            "bytes", "pins", "deferred_evict", "replica"} <= set(row)
    # the replication surface rides the same snapshot (empty heat
    # table and a disarmed hedge timer at the rf=1 default)
    assert doc["rf"] == 1 and doc["replicated"] is False
    assert doc["heat"] == {}
    assert doc["hedge"]["armed"] is False


def test_ownership_metrics_documented():
    """The tempo_search_hbm_owner_* rows must stay in the observability
    catalog (thin wrapper over the drift engine, like the faultpoint
    test)."""
    from tempo_tpu.analysis.drift import catalog_findings

    findings = [f for f in catalog_findings("metric-names")
                if "hbm_owner" in f.message]
    assert not findings, "\n".join(
        f"{f.path}:{f.line}: {f.message}" for f in findings)


def test_noop_contract_registered():
    """The ownership gate rides the static noop-contract checker like
    the planner/query-stats knobs — and the replication/hedge gates
    ride beside it (heat table, replica lookups and the hedge timer
    must each cost one attribute read at rf=1)."""
    from tempo_tpu.analysis.contracts import GATED_FUNCTIONS, GUARDED_CALLS

    knobs = {g.knob for g in GATED_FUNCTIONS}
    assert "search_hbm_ownership_enabled" in knobs
    assert "search_hbm_ownership_rf" in knobs
    assert "search_hbm_ownership_hot_rate" in knobs
    assert "search_hedge_delay_ms" in knobs
    gated = {g.qualname for g in GATED_FUNCTIONS}
    assert {"OwnershipMap.record_access", "OwnershipMap.replica_indices",
            "OwnershipMap.sweep", "HedgeTimer.observe",
            "HedgeTimer.delay_s"} <= gated
    assert any(r.receiver == "OWNERSHIP" for r in GUARDED_CALLS)
    assert any(r.receiver == "HEDGE" and "observe" in r.methods
               for r in GUARDED_CALLS)
    assert any(r.receiver == "OWNERSHIP" and "record_access" in r.methods
               for r in GUARDED_CALLS)


# ------------------------------------- heat-adaptive replication (rf>1)


def test_replica_table_primary_first_distinct():
    """The per-generation replica table: rf distinct ring members per
    group, primary (the owner) first — the frontend's hedge order."""
    ownership.configure(enabled=True, members="h0,h1,h2", self_id="h0",
                        groups=32, rf=2, hot_rate=5.0)
    assert OWNERSHIP.replicated is True
    assert OWNERSHIP._replica_depth == 2
    for g in range(32):
        reps = OWNERSHIP._replicas[g]
        assert len(reps) == 2 and len(set(reps)) == 2
        assert reps[0] == OWNERSHIP._owners[g]


def test_rf_defaults_are_true_noop():
    """rf=1 (the default): the heat table never records, replica
    lookups return empty, the sweep no-ops, the hedge timer stays
    disarmed — single-owner behavior bit for bit."""
    from tempo_tpu.search.ownership import HEDGE

    ownership.configure(enabled=True, members="h0,h1", self_id="h0",
                        groups=32)
    assert OWNERSHIP.rf == 1 and OWNERSHIP.replicated is False
    OWNERSHIP.record_access("blk")  # one attribute read: no heat entry
    assert OWNERSHIP._heat == {}
    assert OWNERSHIP.replica_indices("blk") == ()
    assert OWNERSHIP.replicas_of("blk") == ()
    assert OWNERSHIP.sweep() == 0
    assert HEDGE.armed is False
    t = ownership.HedgeTimer()
    t.observe(1.0)  # disarmed: must not touch the estimator
    assert t._n == 0


def test_record_access_promotes_and_sweep_demotes():
    import time as _t

    ownership.configure(enabled=True, members="h0,h1,h2", self_id="h0",
                        groups=32, rf=2, hot_rate=0.01)
    up0 = obs.hbm_replica_promotions.value(dir="up")
    down0 = obs.hbm_replica_promotions.value(dir="down")
    events: list = []
    OWNERSHIP.set_change_hook(
        lambda g, d, reps: events.append((g, d, reps)))
    # one access books rate 1/30 ≈ 0.033 ≥ the 0.01 threshold: promote
    OWNERSHIP.record_access("blk-0")
    g = OWNERSHIP.group_of("blk-0")
    assert g in OWNERSHIP._promoted
    reps = OWNERSHIP.replicas_of("blk-0")
    assert len(reps) == 2 and reps[0] == OWNERSHIP.owner_of("blk-0")
    assert len(OWNERSHIP.replica_indices("blk-0")) == 2
    assert obs.hbm_replica_promotions.value(dir="up") == up0 + 1
    # every replica owns the promoted group (serves it device-resident);
    # the third member still doesn't
    for m in reps:
        with ownership.self_as(m):
            assert OWNERSHIP.owns_block("blk-0")
            assert OWNERSHIP.is_replica("blk-0")
    (other,) = set(OWNERSHIP.members) - set(reps)
    with ownership.self_as(other):
        assert not OWNERSHIP.owns_block("blk-0")
    # two minutes of silence: the rate decays below the hysteresis
    # floor and the sweep demotes
    assert OWNERSHIP.sweep(now=_t.monotonic() + 120.0) == 1
    assert g not in OWNERSHIP._promoted
    assert OWNERSHIP.replica_indices("blk-0") == ()
    assert obs.hbm_replica_promotions.value(dir="down") == down0 + 1
    # the change hook saw both transitions (fired on background threads)
    deadline = _t.time() + 5
    while _t.time() < deadline and len(events) < 2:
        _t.sleep(0.01)
    assert [e[1] for e in events] == ["up", "down"]
    assert events[0][0] == g and events[0][2] == reps


def test_demotion_is_hysteretic():
    """A group whose rate sits between half the threshold and the
    threshold stays promoted — oscillating around hot_rate must not
    flap replica residency."""
    import time as _t

    ownership.configure(enabled=True, members="h0,h1", self_id="h0",
                        groups=32, rf=2, hot_rate=0.02)
    OWNERSHIP.record_access("blk-0")  # 0.033 ≥ 0.02: promoted
    g = OWNERSHIP.group_of("blk-0")
    assert g in OWNERSHIP._promoted
    # 24 s of decay: rate ≈ 0.015 — under the threshold but above the
    # 0.01 floor. No demotion.
    assert OWNERSHIP.sweep(now=_t.monotonic() + 24.0) == 0
    assert g in OWNERSHIP._promoted


def test_snapshot_heat_and_hedge_shape():
    ownership.configure(enabled=True, members="h0,h1,h2", self_id="h0",
                        groups=32, rf=2, hot_rate=0.01,
                        hedge_delay_ms=25)
    OWNERSHIP.record_access("blk-0")
    snap = OWNERSHIP.snapshot()
    assert snap["rf"] == 2 and snap["replicated"] is True
    assert snap["hot_rate"] == 0.01
    row = snap["heat"][str(OWNERSHIP.group_of("blk-0"))]
    assert row["promoted"] is True and row["rf"] == 2
    assert len(row["replicas"]) == 2
    assert row["rate"] > 0 and "promoted_t" in row
    assert snap["hedge"]["armed"] is True
    assert snap["hedge"]["delay_ms"] == 25.0


def test_hedge_timer_delay_derivation():
    t = ownership.HedgeTimer()
    # disarmed: the default, after one attribute read
    assert t.delay_s() == 0.05
    t.armed = True
    t.fixed_ms = 40.0
    assert t.delay_s() == pytest.approx(0.040)
    t.fixed_ms = 0.0
    # profiler-stage seed carries the estimate before direct samples
    t._on_stage("execute", "device", 0.02, 0)
    assert t.delay_s() == pytest.approx(0.06)
    t._on_stage("header_prune", "host", 9.9, 0)  # not a dispatch stage
    assert t.delay_s() == pytest.approx(0.06)
    # enough direct observations: Jacobson/Karels mean + 3*dev
    for _ in range(12):
        t.observe(0.05)
    assert 0.05 <= t.delay_s() <= 0.2
    t.reset()
    assert t.armed is False and t._n == 0


def test_configure_rf_change_rebuilds_replica_depth():
    """Raising rf after the members installed rebuilds the replica
    table at the new depth (generation bumps: the frontend's plans
    must re-key — routing potential changed)."""
    ownership.configure(enabled=True, members="h0,h1,h2", self_id="h0",
                        groups=32)
    gen = OWNERSHIP.generation
    assert OWNERSHIP._replica_depth == 1
    ownership.configure(rf=2, hot_rate=0.5)
    assert OWNERSHIP._replica_depth == 2
    assert OWNERSHIP.generation == gen + 1
    # idempotent re-configure at the same depth: no churn
    ownership.configure(rf=2, hot_rate=0.5)
    assert OWNERSHIP.generation == gen + 1


def test_group_resize_clears_heat_state():
    ownership.configure(enabled=True, members="h0,h1", self_id="h0",
                        groups=32, rf=2, hot_rate=0.01)
    OWNERSHIP.record_access("blk-0")
    assert OWNERSHIP._promoted
    ownership.configure(groups=64, members="h0,h1")
    # group ids re-hashed: stale heat/promotions describe dead groups
    assert not OWNERSHIP._promoted and OWNERSHIP._heat == {}


# ----------------------------------------- hedged dispatch (frontend)


def test_owner_querier_plan_width_and_replica_preference():
    """Satellite: the owner→querier mapping keys on the PLAN-TIME pool
    width (riding the generation-keyed batch plan), so a pool resize
    mid-flight cannot silently remap every owner; replica retries walk
    the replica set before the round-robin fallback."""
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend

    fe = QueryFrontend(["q0", "q1", "q2"], FrontendConfig())
    # plan-time width pins the mapping even though the live pool is 3
    assert fe._owner_querier(2, 0, 2) == "q0"   # 2 % plan-width 2
    assert fe._owner_querier(1, 0, 2) == "q1"
    # replica preference: attempts 1..rf-1 walk the replica set
    assert fe._owner_querier(2, 0, 3, (2, 0)) == "q2"
    assert fe._owner_querier(2, 1, 3, (2, 0)) == "q0"
    # past the replica set: round-robin fallback
    assert fe._owner_querier(2, 2, 3, (2, 0)) in ("q0", "q1", "q2")
    # a plan index past a SHRUNK pool degrades to round-robin, never an
    # IndexError or an arbitrary wrong owner
    small = QueryFrontend(["q0", "q1"], FrontendConfig())
    assert small._owner_querier(5, 0, 6) in ("q0", "q1")


class _FakeQuerier:
    """search_blocks stub with a programmable wall/failure — the
    hedged-send race harness. Checks the per-attempt deadline between
    'groups' like the real batcher, so a cancelled loser stops early."""

    def __init__(self, resp, delay_s=0.0, fail=False):
        self.resp = resp
        self.delay_s = delay_s
        self.fail = fail
        self.calls = 0
        self.cancelled = 0

    def search_blocks(self, breq):
        from tempo_tpu.robustness import deadline as _dl
        import time as _t

        self.calls += 1
        t_end = _t.monotonic() + self.delay_s
        while _t.monotonic() < t_end:
            if _dl.expired():
                self.cancelled += 1
                raise robustness.DeadlineExceeded("cancelled mid-scan")
            _t.sleep(0.005)
        if self.fail:
            raise RuntimeError("querier died")
        return self.resp


def _hedge_armed(fixed_ms=20.0):
    from tempo_tpu.search.ownership import HEDGE

    HEDGE.armed = True
    HEDGE.fixed_ms = fixed_ms
    return HEDGE


def test_hedged_send_primary_wins_inside_delay():
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend

    _hedge_armed(fixed_ms=50.0)
    primary = _FakeQuerier("fast", delay_s=0.0)
    hedge = _FakeQuerier("never", delay_s=0.0)
    fe = QueryFrontend([primary, hedge], FrontendConfig())
    before = obs.hedged_dispatches.value(result="primary")
    r = fe._hedged_send(tempopb.SearchBlocksRequest(), primary, hedge)
    assert r == "fast"
    assert hedge.calls == 0  # the hedge never fired
    assert obs.hedged_dispatches.value(result="primary") == before + 1


def test_hedged_send_replica_wins_and_loser_cancelled():
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend

    _hedge_armed(fixed_ms=20.0)
    primary = _FakeQuerier("slow", delay_s=5.0)   # wedged past the delay
    hedge = _FakeQuerier("fast", delay_s=0.0)
    fe = QueryFrontend([primary, hedge], FrontendConfig())
    won0 = obs.hedged_dispatches.value(result="hedge_won")
    can0 = obs.hedged_dispatches.value(result="cancelled")
    r = fe._hedged_send(tempopb.SearchBlocksRequest(), primary, hedge)
    assert r == "fast"
    assert hedge.calls == 1
    assert obs.hedged_dispatches.value(result="hedge_won") == won0 + 1
    assert obs.hedged_dispatches.value(result="cancelled") == can0 + 1
    # the loser's force-expired deadline stops it at the next check —
    # it must not burn its full 5 s wall
    deadline = __import__("time").time() + 3
    while __import__("time").time() < deadline and not primary.cancelled:
        __import__("time").sleep(0.01)
    assert primary.cancelled == 1


def test_hedged_send_fast_primary_failure_raises_for_retry():
    """A primary that FAILS inside the hedge delay raises immediately —
    _retrying moves to the surviving replica (attempt 1 prefers it)
    instead of waiting out the delay."""
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend

    _hedge_armed(fixed_ms=5000.0)  # the delay must not be waited out
    primary = _FakeQuerier(None, delay_s=0.0, fail=True)
    hedge = _FakeQuerier("alive", delay_s=0.0)
    fe = QueryFrontend([primary, hedge], FrontendConfig())
    t0 = __import__("time").monotonic()
    with pytest.raises(RuntimeError, match="querier died"):
        fe._hedged_send(tempopb.SearchBlocksRequest(), primary, hedge)
    assert __import__("time").monotonic() - t0 < 2.0
    assert hedge.calls == 0


def test_dispatch_batch_hedges_only_promoted_groups(tmp_path):
    """End to end through _dispatch_batch: an un-promoted group keeps
    the exact rf=1 single dispatch; a promoted one hedges and stays
    byte-identical."""
    db, proxies, fe = _frontend(tmp_path)
    req = _req(limit=10_000)
    base = _canon(fe.search("t", req))
    ownership.configure(enabled=True, members="m0,m1", self_id="m0",
                        groups=32, rf=2, hot_rate=0.01,
                        hedge_delay_ms=15)
    for p in proxies:
        p.block_batches.clear()
    calls_before = sum(len(p.block_batches) for p in proxies)
    assert calls_before == 0
    # not promoted yet: no hedging, one dispatch per batch
    assert _canon(fe.search("t", req)) == base
    batches = fe._search_batches("t")
    n_owned = sum(1 for b in batches if b[2] is not None)
    assert sum(len(p.block_batches) for p in proxies) == n_owned
    # promote every group, wedge the primaries: the hedge answers and
    # the response stays byte-identical
    for m in db.blocklist.metas("t"):
        OWNERSHIP.record_access(m.block_id)
    won0 = obs.hedged_dispatches.value(result="hedge_won")

    class _SlowFirst:
        """Delay injected around member-0's querier only."""

        def __init__(self, inner):
            self.inner = inner
            self.db = inner.db

        def search_recent(self, tenant, req):
            return self.inner.search_recent(tenant, req)

        def search_blocks(self, breq):
            __import__("time").sleep(0.25)
            return self.inner.search_blocks(breq)

    fe.queriers[0] = _SlowFirst(proxies[0])
    got = _canon(fe.search("t", req))
    assert got == base
    # at least one batch was owned by the slow member: its hedge won
    if any(b[2] == 0 for b in batches):
        assert obs.hedged_dispatches.value(result="hedge_won") > won0
