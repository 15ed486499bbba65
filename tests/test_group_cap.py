"""A group is what one device scans in a launch: `max_batch_pages`
counts pages per device, so a mesh of s devices groups s times as many
(`BlockBatcher.group_cap`, read when a plan is made).

Planning is host-side: `engine.n_shards` is set by hand, as
`TempoDB._ensure_mesh` sets it, and no mesh is needed. The served side
(answers against the reference, launches per search) is
`test_mesh_served.py`'s."""

import zlib
from collections import Counter

import pytest

from tempo_tpu import tempopb
from tempo_tpu.search import multiblock
from tempo_tpu.search.batcher import BlockBatcher, ScanJob

from tests.test_db import _db, _ingest, _mk_req, _synthetic_jobs

SHARDS = (1, 2, 4)


def _batcher(shards, **kw):
    b = BlockBatcher(**kw)
    b.engine.n_shards = shards
    return b


def _bench_jobs(config_name, n_blocks, n_pages=64):
    """The benchmark's blocks as the batcher sees them: ids from the
    generator's own rule, 64 pages of 1,024 entries a block."""
    from chipbench.generators.otel_blocks import block_id

    return [ScanJob(key=(block_id(config_name, i, n_pages), 0, n_pages),
                    pages_fn=None, header={"n_pages": n_pages},
                    n_pages=n_pages, n_entries=n_pages * 1024,
                    geometry=(1024, 16))
            for i in range(n_blocks)]


def _plan_before_the_cap_was_per_device(jobs, max_batch_pages=4096):
    """The rule as it stood when the cap was `max_batch_pages` pages
    whatever the mesh, restated (one geometry)."""
    groups, cur, cur_pages = [], [], 0
    for j in sorted(jobs, key=lambda j: j.key):
        divisor = max(2, max_batch_pages // (2 * max(1, j.n_pages)))
        cuts = zlib.crc32(repr(j.key).encode()) % divisor == 0
        if cur and (cur_pages + j.n_pages > max_batch_pages
                    or (cur_pages >= max_batch_pages // 4 and cuts)):
            groups.append(cur)
            cur, cur_pages = [], 0
        cur.append(j)
        cur_pages += j.n_pages
    return groups + ([cur] if cur else [])


@pytest.mark.parametrize("shards", SHARDS)
def test_plan_closes_groups_at_the_cap_for_each_device(shards):
    """Random-looking ids (cut anchors and all): no group over
    `max_batch_pages * shards` pages, groups past the one-device cap on
    a mesh, none off it, every job once, and fewer groups than one
    device plans."""
    b = _batcher(shards, max_batch_pages=512)
    assert b.group_cap() == 512 * shards
    jobs = _synthetic_jobs(200) + _synthetic_jobs(8, n_pages=300,
                                                  prefix="big")
    groups = b.plan(jobs)
    sizes = [sum(j.n_pages for j in g) for g in groups]
    assert max(sizes) <= 512 * shards
    assert (max(sizes) > 512) == (shards > 1)
    assert sorted(j.key for g in groups for j in g) == sorted(
        j.key for j in jobs)
    one = _batcher(1, max_batch_pages=512).plan(jobs)
    assert len(groups) == len(one) if shards == 1 else len(groups) < len(one)
    # where a block's pages divide the cap (64 into 512), a mesh's
    # anchors are a subset of one device's, and some remain
    mesh_anchors = [j for j in jobs
                    if j.n_pages == 64 and b._cuts(j, b.group_cap())]
    assert all(b._cuts(j, 512) for j in mesh_anchors)
    assert mesh_anchors


@pytest.mark.parametrize("shards,config_name,blocks,want", [
    (1, "tempo-search-share16", 625, {64: 9, 49: 1}),
    (1, "tempo-search-share16x4", 1536, {64: 24}),
    (1, "tempo-search-share4", 1536, {64: 24}),
    (2, "tempo-search-share16x4", 1536, {128: 12}),
    (4, "tempo-search-share16x4", 1536, {256: 6}),
])
def test_the_benchmarks_ids_fill_the_groups(shards, config_name, blocks,
                                            want):
    """The benchmark's ids step past every `crc % 32 == 0`, and a mesh's
    divisor is a multiple of 32, so its groups fill to the cap on any
    mesh: one group size, 1,536 blocks -> 6 x 256 on four devices. On
    one device the plan is the one the old rule made, key for key."""
    jobs = _bench_jobs(config_name, blocks)
    groups = _batcher(shards).plan(jobs)
    assert Counter(len(g) for g in groups) == want
    if shards == 1:
        old = _plan_before_the_cap_was_per_device(jobs)
        assert [[j.key for j in g] for g in groups] == [
            [j.key for j in g] for g in old]


@pytest.mark.parametrize("shards", SHARDS + (3, 8))
def test_a_launch_reads_at_most_the_cap_on_each_device(shards, monkeypatch):
    """Padding included: every planned group, padded as the engine
    stages it (a power of two aligned to the shards), gives each device
    at most `max_batch_pages` pages, at the served size and with ragged
    blocks."""
    padded = []
    monkeypatch.setattr(
        multiblock, "stack_host",
        lambda blocks, pad_to, **kw: padded.append(pad_to) or pad_to)
    b = _batcher(shards)
    jobs = (_bench_jobs("tempo-search-share16x4", 700)
            + _synthetic_jobs(300, n_pages=37, prefix="ragged")
            + _synthetic_jobs(40, n_pages=255, prefix="wide"))
    groups = b.plan(jobs)
    for g in groups:
        b.engine.stage_host(g)          # a job has `n_pages`, like a block
    assert len(padded) == len(groups)
    for pad_to in padded:
        assert pad_to % shards == 0
        assert pad_to // shards <= b.max_batch_pages
    # and the full groups give each device exactly the one-device launch
    assert max(padded) // shards == b.max_batch_pages


def _searched(db, tenant, req):
    resp = db.search(tenant, req).response()
    return ({t.trace_id for t in resp.traces},
            resp.metrics.inspected_traces)


@pytest.mark.parametrize("shards", SHARDS)
def test_a_cached_plan_does_not_outlive_its_cap(tmp_path, shards,
                                                monkeypatch):
    """The plan memo is keyed by the cap: a plan made before a mesh was
    attached is made again after it, with the mesh's cap (and the same
    answers); with no mesh attached the memo is hit.

    Only the cap closes a group here. The blocks' ids are uuid4, and at
    caps of 2 and 4 pages every other id is a cut anchor: about one draw
    in four (26 % of 2,000 at two shards) cut the eight blocks into as
    many groups under both caps, and `fewer groups on the mesh` failed
    on the draw, not on the memo. Anchors are `test_plan_closes_groups_at_the_cap...`'s."""
    from tempo_tpu.parallel import make_mesh

    monkeypatch.setattr(BlockBatcher, "_cuts",
                        staticmethod(lambda j, cap: False))
    db = _db(tmp_path, auto_mesh=False, search_max_batch_pages=2)
    for b in range(8):
        _ingest(db, "t1", 4, seed_base=b * 50)
    db.poll()
    req = _mk_req({})
    req.limit = 10_000
    want = _searched(db, "t1", req)
    gen1, groups1 = db.batcher._plan_cache["t1"]
    assert gen1[-1] == 2 and max(len(g) for g in groups1) <= 2
    if shards > 1:
        db.mesh = make_mesh(shards)
        db.batcher.engine.mesh = db.mesh
        db.batcher.engine.n_shards = shards
        # what the old layout staged is not this mesh's
        db.batcher.cache.invalidate(set())
    assert _searched(db, "t1", req) == want
    gen2, groups2 = db.batcher._plan_cache["t1"]
    assert gen2[-1] == 2 * shards
    if shards == 1:
        assert groups2 is groups1
    else:
        assert len(groups2) < len(groups1)
        assert max(sum(j.n_pages for j in g) for g in groups2) > 2

    # the job-request path memoises its plan too, under the same rule
    breq = tempopb.SearchBlocksRequest()
    breq.tenant_id = "t1"
    breq.search_req.CopyFrom(req)
    for m in db.blocklist.metas("t1"):
        j = breq.jobs.add()
        j.block_id, j.encoding = m.block_id, m.encoding
        j.version, j.data_encoding = m.version, m.data_encoding
    resp = db.search_blocks(breq).response()
    assert ({t.trace_id for t in resp.traces},
            resp.metrics.inspected_traces) == want
    (epoch, *_rest, groups3), = db._breq_jobs_cache.values()
    assert epoch[-1] == 2 * shards
    assert [[j.key for j in g] for g in groups3] == [
        [j.key for j in g] for g in groups2]


def test_prewarm_plans_after_the_mesh_is_resolved(tmp_path):
    """`auto_mesh` resolves lazily, after the batcher is built: a poll's
    prewarm resolves it before it plans, so what it stages are the
    mesh's groups (up to sixteen pages each on eight devices), never
    groups cut at one device's cap that no search would then ask for."""
    import jax

    n = len(jax.devices())
    assert n > 1
    db = _db(tmp_path, search_max_batch_pages=2)
    for b in range(12):
        _ingest(db, "t1", 4, seed_base=b * 40)
    db.cfg.search_prewarm_on_poll = False
    db.poll()
    assert db.batcher.engine.n_shards == 1 and db.batcher.group_cap() == 2
    staged = db.prewarm(["t1"], background=False)
    assert db.batcher.engine.n_shards == n
    assert db.batcher.group_cap() == 2 * n
    keys = list(db.batcher.cache.snapshot()["entries"])
    # (12 one-page blocks: 6 groups or more at one device's cap of 2)
    assert staged == len(keys) <= 3 and sum(map(len, keys)) == 12
    assert max(map(len, keys)) > 2
    # and the first search asks for exactly that group
    req = _mk_req({})
    req.limit = 10_000
    ids, inspected = _searched(db, "t1", req)
    assert len(ids) == 48 and inspected == 48
    _gen, groups = db.batcher._plan_cache["t1"]
    assert [tuple(j.key for j in g) for g in groups] == keys
