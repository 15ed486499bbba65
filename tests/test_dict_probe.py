"""Device-resident dictionary probe (PR4 tentpole).

The substring prefilter moves from the host (numpy char.find / native
memmem) onto the device as a rolling-window kernel over the packed
dictionary bytes (tempo_tpu/search/dict_probe.py). These tests pin the
contract from ISSUE 4's acceptance criteria:

  - differential parity: device probe ≡ host substring_value_ids ≡
    native substr_scan over random unicode dictionaries and needles
    (empty needle, multi-byte chars, needles spanning value boundaries);
  - match results byte-identical to the host path through every
    dispatch shape: one-block batch, multi-block (mixed device/host
    blocks), coalesced multi-query, and mesh-sharded;
  - HBM accounting covers the staged dictionary arrays, and an
    HBM-evicted batch re-uploads its dictionaries on re-stage without
    re-packing the host side.
"""

import random
import threading

import numpy as np
import pytest

from tempo_tpu import tempopb
from tempo_tpu.search import dict_probe, pipeline
from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
from tempo_tpu.search.data import SearchData
from tempo_tpu.search.pipeline import compile_query, substring_value_ids
from tempo_tpu.search.multiblock import (
    MultiBlockEngine,
    compile_multi,
    stack_blocks,
    stack_queries,
)

from conftest import check_budget, scan_batch, staged_dict
from tempo_tpu.search.batcher import BlockBatcher


@pytest.fixture(autouse=True)
def _fresh_compile_cache():
    """The global compile cache deliberately serves a cached host-path
    probe product to device-capable callers (both are exact); parity
    tests that compare the two paths must start cold."""
    pipeline._COMPILE_CACHE.clear()
    yield
    pipeline._COMPILE_CACHE.clear()


@pytest.fixture(params=["mask", "range"])
def membership(request, monkeypatch):
    """How the device probe's product leaves it: `range` is the shipped
    rule (hits of at most dict_probe.R_MAX runs leave as [lo, hi] id
    ranges, as these corpora's all do), `mask` holds R_MAX at 0 so that
    every hit leaves as the [T, v_pad] mask."""
    if request.param == "mask":
        monkeypatch.setattr(dict_probe, "R_MAX", 0)
    return request.param


def _mk_req(tags=None, **kw):
    req = tempopb.SearchRequest()
    for k, v in (tags or {}).items():
        req.tags[k] = v
    for k, v in kw.items():
        setattr(req, k, v)
    return req


def _probe_ids(val_dict, needles, n_shards=1, mesh=None):
    dd = dict_probe.stage_val_dict(val_dict, n_shards=n_shards, mesh=mesh)
    hits, any_hits = dict_probe.probe_value_hits(
        dd, [n.encode("utf-8") for n in needles])
    hits = np.asarray(hits)
    any_hits = np.asarray(any_hits)
    out = []
    for t in range(len(needles)):
        ids = dict_probe.hits_to_ids(hits[t])
        assert bool(any_hits[t]) == (ids.size > 0)
        assert not hits[t, len(val_dict):].any(), "padding values lit up"
        out.append(ids)
    return out


# ---------------------------------------------------------------------------
# kernel-level differential parity


def test_probe_matches_host_on_fixed_edges():
    """The edge cases named in ISSUE 4: empty needle, multi-byte chars,
    a needle that only exists ACROSS a value boundary (must not match),
    zero-length values, needle == whole value."""
    vd = sorted(["", "ab", "cd", "alpha", "alphabet", "βeta", "日本語",
                 "日本", "a" * 40, "xx-日本-yy"])
    needles = ["", "ab", "bc",       # "bc" spans ab|cd in the packed buf
               "alpha", "日本", "語", "βeta", "a" * 40, "a" * 41, "zzz"]
    got = _probe_ids(vd, needles)
    for needle, ids in zip(needles, got):
        want = substring_value_ids(vd, needle)
        assert ids.tolist() == want.tolist(), needle


def test_probe_matches_host_property():
    """Random unicode dictionaries × random needles, several size/needle
    buckets; the device kernel must agree exactly with the host scan."""
    charset = "abcdefgh0123-_αβγ日本語🎉"
    rng = random.Random(99)
    for round_ in range(6):
        n_vals = rng.choice([7, 33, 70])
        vd = sorted({
            "".join(rng.choice(charset)
                    for _ in range(rng.randint(0, 12)))
            for _ in range(n_vals)
        })
        needles = []
        for _ in range(rng.randint(1, 4)):
            if rng.random() < 0.3 and vd:
                src = rng.choice(vd)  # sampled substring: real hits
                if src:
                    i = rng.randrange(len(src))
                    needles.append(src[i:i + rng.randint(1, 6)])
                    continue
            needles.append("".join(rng.choice(charset)
                                   for _ in range(rng.randint(0, 5))))
        got = _probe_ids(vd, needles)
        for needle, ids in zip(needles, got):
            want = substring_value_ids(vd, needle)
            assert ids.tolist() == want.tolist(), (round_, needle, vd)


def test_probe_matches_native_scan():
    from tempo_tpu.ops import native
    from tempo_tpu.search.pipeline import pack_val_dict

    if not native.available():
        pytest.skip("native lib unavailable")
    vd = sorted({f"val-{i:05d}-{'x' if i % 3 else 'special'}"
                 for i in range(2_000)})
    buf, offsets = pack_val_dict(vd)
    needles = ["special", "val-0001", "", "zzz", "-x"]
    got = _probe_ids(vd, needles)
    for needle, ids in zip(needles, got):
        want = native.substr_scan(buf, offsets, needle.encode()).tolist()
        assert ids.tolist() == want, needle


def test_probe_sharded_matches_unsharded():
    """The value axis splits into shards and the per-shard masks
    all_gather back — global ids must be identical to the S=1 probe.
    Uses the mesh over the test process's CPU devices."""
    from tempo_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    vd = sorted({f"session-{i:05d}" for i in range(1_000)}
                | {"", "x", "sess"})
    needles = ["session-0001", "sess", "", "zzz", "05"]
    flat = _probe_ids(vd, needles)
    sharded = _probe_ids(vd, needles,
                         n_shards=int(mesh.devices.size), mesh=mesh)
    for needle, a, b in zip(needles, flat, sharded):
        assert a.tolist() == b.tolist(), needle


def test_probe_sharded_pack_placed_unsharded_probes_every_shard():
    """A dictionary packed for an S-way mesh but placed WITHOUT the mesh
    (place_batch's shard-mismatch fallback) must still probe every
    shard's value range — the single-device kernel vmaps over the shard
    axis, it does not silently drop shards 1..S-1."""
    vd = sorted({f"session-{i:05d}" for i in range(500)} | {"", "tail-zz"})
    needles = ["session-0049", "tail", "", "zzz"]
    flat = _probe_ids(vd, needles)
    packed4 = _probe_ids(vd, needles, n_shards=4)  # no mesh passed
    for needle, a, b in zip(needles, flat, packed4):
        assert a.tolist() == b.tolist(), needle
        assert a.tolist() == substring_value_ids(vd, needle).tolist()


def test_one_block_search_honors_probe_threshold():
    """One block searched alone goes through the batcher, so it honors
    cfg's threshold as every search does: <= 0 keeps the probe on the
    host, a small threshold stages the dictionary and yields identical
    results."""
    from tempo_tpu.backend import BlockMeta, MockBackend
    from tempo_tpu.search.backend_search_block import (
        BackendSearchBlock,
        write_search_block,
    )

    be = MockBackend()
    meta = BlockMeta(tenant_id="t1")
    write_search_block(be, meta, _corpus(200, seed=7), PageGeometry(32, 8))
    req = _mk_req({"session.id": "session-00"}, limit=500)
    jobs = [BackendSearchBlock(be, meta).scan_job()]

    def staged_dicts(b):
        (gkey,) = b.cache.snapshot()["entries"]
        return b.cache.resident(gkey).batch.staged_dicts

    off = BlockBatcher(device_probe_min_vals=-1)
    r_off = off.search(jobs, req).response().SerializeToString()
    assert not staged_dicts(off)

    pipeline._COMPILE_CACHE.clear()
    on = BlockBatcher(device_probe_min_vals=1)
    assert on.search(jobs, req).response().SerializeToString() == r_off
    assert staged_dicts(on)


def test_probe_rejects_oversized_needle():
    dd = dict_probe.stage_val_dict(["aa", "bb"])
    with pytest.raises(ValueError):
        dict_probe.probe_value_hits(
            dd, [b"x" * (dict_probe.MAX_NEEDLE_BYTES + 1)])


# ---------------------------------------------------------------------------
# corpora for the dispatch-path tests


def _corpus(n, seed, card=300):
    rng = random.Random(seed)
    out = []
    for i in range(n):
        tid = (seed.to_bytes(2, "big") + i.to_bytes(4, "big")).rjust(16, b"\x00")
        sd = SearchData(trace_id=tid)
        # unique start seconds: top-k tie-breaks are documented as
        # unordered, byte-identity must not depend on them
        sd.start_s = 1_600_000_000 + seed * 1_000_000 + i
        sd.end_s = sd.start_s + 5
        sd.dur_ms = rng.randint(1, 30_000)
        sd.kvs = {"session.id": {f"session-{rng.randint(0, card - 1):04d}"},
                  "svc": {rng.choice(["frontend", "cart"])}}
        out.append(sd)
    return out


def _blocks(n=3, entries=150, small_tail=True):
    blocks = [ColumnarPages.build(_corpus(entries, seed=s),
                                  PageGeometry(32, 8)) for s in range(n)]
    if small_tail:  # one low-cardinality block that stays on the host path
        blocks.append(ColumnarPages.build(_corpus(80, seed=9, card=3),
                                          PageGeometry(32, 8)))
    return blocks


# ---------------------------------------------------------------------------
# one-block batches


def test_one_block_batch_device_probe_byte_identical(membership):
    pages = ColumnarPages.build(_corpus(300, seed=1), PageGeometry(64, 8))
    req = _mk_req({"session.id": "session-00"}, limit=1000)

    host = scan_batch([pages], req, top_k=1024, probe_min_vals=0)
    assert not host.batch.staged_dicts
    assert host.mq.val_hits is None

    pipeline._COMPILE_CACHE.clear()
    dev = scan_batch([pages], req, top_k=1024, probe_min_vals=1)
    assert dev.batch.staged_dicts
    assert (dev.mq.val_hits is not None) == (membership == "mask")

    assert host.out[:2] == dev.out[:2]
    r_h = [(m.trace_id, m.start_time_unix_nano) for m in host.metas]
    r_d = [(m.trace_id, m.start_time_unix_nano) for m in dev.metas]
    assert r_h == r_d

    # prune parity: a needle no dictionary value contains prunes on both
    miss = _mk_req({"session.id": "zzz-absent"})
    assert compile_multi([pages], miss, cache_on=dev.batch) is None
    assert compile_multi([pages], miss) is None


def test_oversized_needle_falls_back_to_exact_host_path():
    pages = ColumnarPages.build(_corpus(120, seed=2), PageGeometry(32, 8))
    sd = staged_dict(pages)
    long_needle = "x" * (dict_probe.MAX_NEEDLE_BYTES + 1)
    req = _mk_req({"session.id": long_needle, "svc": "frontend"},
                  limit=100)
    # must not raise — the whole query drops to the host scan
    cq = compile_query(pages.key_dict, pages.val_dict, req,
                       staged_dict=sd)
    assert cq is None  # nothing contains a 65-byte needle → pruned
    req2 = _mk_req({"svc": "front" + "t" * dict_probe.MAX_NEEDLE_BYTES})
    assert compile_query(pages.key_dict, pages.val_dict, req2,
                         staged_dict=sd) is None


def test_exhaustive_flag_with_device_probe():
    """Under the exhaustive debug tag a missing key / empty-match term
    must scan (and match nothing), not prune — same semantics as host."""
    pages = ColumnarPages.build(_corpus(100, seed=3), PageGeometry(32, 8))
    req = _mk_req({"absent.key": "x",
                   pipeline.EXHAUSTIVE_SEARCH_TAG: "1"}, limit=50)
    got = scan_batch([pages], req, top_k=64, probe_min_vals=1)
    assert got.batch.staged_dicts and got.mq is not None
    assert got.count == 0 and got.inspected == 100


def test_compile_cache_skips_device_probe_work(membership):
    """Repeated tag-sets must hit the compile cache without re-running
    the probe kernel (same contract as the host path's cache)."""
    from unittest import mock

    pages = ColumnarPages.build(_corpus(150, seed=4), PageGeometry(32, 8))
    sd = staged_dict(pages)
    req = _mk_req({"session.id": "session-01"}, limit=20)
    with mock.patch.object(dict_probe, "probe_values",
                           wraps=dict_probe.probe_values) as probe:
        cq1 = compile_query(pages.key_dict, pages.val_dict, req,
                            cache_on=pages, staged_dict=sd)
        assert cq1 is not None and probe.call_count == 1
        cq2 = compile_query(pages.key_dict, pages.val_dict, req,
                            cache_on=pages, staged_dict=sd)
        assert probe.call_count == 1  # cache hit: no second dispatch
        assert cq2.val_hits is cq1.val_hits
        assert cq2.val_ranges is cq1.val_ranges
        assert (cq1.val_hits is not None) == (membership == "mask")


# ---------------------------------------------------------------------------
# multi-block / coalesced / mesh dispatch paths


def test_multiblock_mixed_device_and_host_blocks(membership):
    """High-cardinality blocks probe on device while the small block
    keeps host ranges, in ONE batch — results byte-identical to the
    all-host compile."""
    blocks = _blocks()
    req = _mk_req({"session.id": "session-00"}, limit=1000)
    eng = MultiBlockEngine(top_k=1024)

    batch_host = stack_blocks(blocks, pad_to=32)
    mq_host = compile_multi(blocks, req)
    out_h = eng.scan(batch_host, mq_host)

    pipeline._COMPILE_CACHE.clear()
    batch_dev = stack_blocks(blocks, pad_to=32, probe_min_vals=50)
    assert len(batch_dev.staged_dicts) == 3  # the small block stays host
    mq_dev = compile_multi(blocks, req, cache_on=batch_dev)
    if membership == "mask":
        assert (mq_dev.block_group >= 0).sum() == 3
        assert mq_dev.block_group[3] == -1
    else:           # the device's runs are ranges like the host's
        assert mq_dev.val_hits is None and mq_dev.block_group is None
        assert np.array_equal(mq_dev.val_ranges, mq_host.val_ranges)
    out_d = eng.scan(batch_dev, mq_dev)

    assert out_h[0] == out_d[0] and out_h[1] == out_d[1]
    r_h = [(m.trace_id, m.start_time_unix_nano) for m in
           eng.results(batch_host, mq_host, out_h[2], out_h[3])]
    r_d = [(m.trace_id, m.start_time_unix_nano) for m in
           eng.results(batch_dev, mq_dev, out_d[2], out_d[3])]
    assert r_h == r_d


def test_multiblock_header_skip_masks_device_probed_block(membership):
    from tempo_tpu.search.data import search_data_matches

    blocks = _blocks(n=2, small_tail=False)
    req = _mk_req({"session.id": "session-0"}, limit=1000)
    batch = stack_blocks(blocks, probe_min_vals=10)
    mq = compile_multi(blocks, req, skip=[True, False], cache_on=batch)
    assert mq is not None
    if membership == "mask":
        assert mq.block_group[0] == -1      # skipped row: range path,
    assert (mq.term_keys[0] == -1).all()    # unmatchable sentinel
    eng = MultiBlockEngine(top_k=1024)
    count, _, scores, idx = eng.scan(batch, mq)
    # only block 1's matches survive — block 0 was header-skipped
    expected = {sd.trace_id for sd in _corpus(150, seed=1)
                if search_data_matches(sd, req)}
    assert count == len(expected)
    got = {bytes.fromhex(m.trace_id)
           for m in eng.results(batch, mq, scores, idx)}
    assert got == expected


def test_coalesced_dispatch_with_device_probe_queries(membership):
    """Fused multi-query dispatch where some members carry device hit
    masks and others compiled through the host path — every member's
    fused result equals its solo dispatch."""
    blocks = _blocks()
    batch = stack_blocks(blocks, pad_to=32, probe_min_vals=50)
    eng = MultiBlockEngine(top_k=1024)
    mqs = []
    for v in ("session-001", "session-01"):
        mqs.append(compile_multi(blocks, _mk_req({"session.id": v},
                                                 limit=1000),
                                 cache_on=batch))
    mqs.append(compile_multi(blocks, _mk_req({}, min_duration_ms=10_000,
                                             limit=1000),
                             cache_on=batch))
    mqs = [m for m in mqs if m is not None]
    masked = membership == "mask"
    assert any(m.val_hits is not None for m in mqs) == masked
    assert any(m.val_hits is None for m in mqs)

    cq = stack_queries(mqs)
    assert (cq.val_hits is not None) == masked
    counts, inspected, scores, idx = eng.fetch(
        eng.coalesced_scan_async(batch, cq, 1024), cq)
    for qi, mq in enumerate(mqs):
        s_count, _, s_scores, s_idx = eng.scan(batch, mq)
        assert counts[qi] == s_count
        assert np.array_equal(scores[qi][:s_scores.shape[0]], s_scores)


def test_mesh_sharded_dispatch_with_device_probe(membership):
    """The dictionary shards along the value axis over the mesh, the
    hit masks all_gather, and the sharded scan consumes them — results
    identical to the unsharded host-path scan."""
    from tempo_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    blocks = _blocks(n=2, entries=256, small_tail=False)
    req = _mk_req({"session.id": "session-00"}, limit=1000)

    eng = MultiBlockEngine(top_k=1024, mesh=mesh, device_probe_min_vals=50)
    batch = eng.stage(blocks)
    assert len(batch.staged_dicts) == 2
    assert all(dd.mesh is not None for dd in batch.staged_dicts.values())
    mq = compile_multi(blocks, req, cache_on=batch)
    assert (mq.val_hits is not None) == (membership == "mask")
    out_mesh = eng.scan(batch, mq)

    pipeline._COMPILE_CACHE.clear()
    eng_h = MultiBlockEngine(top_k=1024)
    batch_h = eng_h.stage(blocks)
    mq_h = compile_multi(blocks, req, cache_on=batch_h)
    assert mq_h.val_hits is None
    out_h = eng_h.scan(batch_h, mq_h)

    assert out_mesh[0] == out_h[0] and out_mesh[1] == out_h[1]
    r_m = {m.trace_id for m in eng.results(batch, mq,
                                           out_mesh[2], out_mesh[3])}
    r_h = {m.trace_id for m in eng_h.results(batch_h, mq_h,
                                             out_h[2], out_h[3])}
    assert r_m == r_h

    # mesh + coalesced + device probe in one dispatch
    mqs = [compile_multi(blocks, _mk_req({"session.id": v}, limit=1000),
                         cache_on=batch)
           for v in ("session-001", "session-01")]
    mqs = [m for m in mqs if m is not None]
    cq = stack_queries(mqs)
    counts = eng.fetch(eng.coalesced_scan_async(batch, cq, 1024), cq)[0]
    for qi, m in enumerate(mqs):
        assert counts[qi] == eng.scan(batch, m)[0]


# ---------------------------------------------------------------------------
# batcher: HBM accounting, eviction/re-stage, concurrent coalescing


def _jobs(blocks):
    from tempo_tpu.search.batcher import ScanJob

    jobs = []
    for i, p in enumerate(blocks):
        jobs.append(ScanJob(
            key=(f"blk-{i:03d}", 0, p.n_pages), pages_fn=(lambda p=p: p),
            header=dict(p.header), n_pages=p.n_pages,
            n_entries=p.n_entries,
            geometry=(p.header["entries_per_page"],
                      p.header["kv_per_entry"])))
    return jobs


def test_batcher_accounts_staged_dict_bytes():
    from tempo_tpu.search.batcher import BlockBatcher

    blocks = _blocks(n=2, small_tail=False)
    b = BlockBatcher(coalesce_max_queries=1, device_probe_min_vals=10)
    req = _mk_req({"session.id": "session-01"}, limit=100)
    b.search(_jobs(blocks), req)
    entries = b.cache.snapshot()["entries"]
    assert entries, "nothing staged"
    entry = b.cache.resident(next(iter(entries)))
    page_bytes = sum(int(a.nbytes) for a in entry.batch.device.values())
    dict_bytes = sum(d.nbytes for d in entry.batch.staged_dicts.values())
    assert dict_bytes > 0
    assert entry.batch.nbytes == page_bytes + dict_bytes
    # the budget counter tracks the full entry sizes
    check_budget(b.cache)


def test_evicted_batch_restages_dictionaries():
    """HBM eviction must leave the host PACKED dictionaries in the host
    tier; the re-stage re-uploads fresh device arrays (one H2D) with the
    byte accounting intact — and never re-packs the strings."""
    from tempo_tpu.search.batcher import BlockBatcher

    blocks = _blocks(n=2, entries=200, small_tail=False)
    # max_batch_pages below two blocks' pages → one group per block
    b = BlockBatcher(max_batch_pages=8, coalesce_max_queries=1,
                     device_probe_min_vals=10)
    req = _mk_req({"session.id": "session-01"}, limit=100)
    r1 = b.search(_jobs(blocks), req).response().SerializeToString()
    snap = b.cache.snapshot()
    assert len(snap["entries"]) == 2 and len(snap["host"]) == 2
    old_dicts = {k: dict(b.cache.resident(k).batch.staged_dicts)
                 for k in snap["entries"]}
    assert all(d for d in old_dicts.values())
    packed_before = [getattr(blk, "_device_dict_packed", None)
                     for blk in blocks]
    assert all(p is not None for p in packed_before)

    # evict the LRU group from HBM (blocklist churn) — the
    # host tier keeps the stacked arrays AND the packed dictionaries
    victim = next(iter(b.cache.snapshot()["entries"]))
    with b.cache.group_lock:
        b.cache._remove_locked(victim)
    check_budget(b.cache)

    pipeline._COMPILE_CACHE.clear()
    r2 = b.search(_jobs(blocks), req).response().SerializeToString()
    assert r2 == r1
    # the evicted group re-staged through the host tier with NEW device
    # dictionary arrays (one fresh H2D upload), the host packing reused
    entry = b.cache.resident(victim)
    assert entry is not None and entry.batch.staged_dicts
    for fp, dd in entry.batch.staged_dicts.items():
        assert old_dicts[victim][fp] is not dd          # re-uploaded
        assert old_dicts[victim][fp].packed is dd.packed  # not re-packed
    packed_after = [getattr(blk, "_device_dict_packed", None)
                    for blk in blocks]
    assert all(a is p for a, p in zip(packed_after, packed_before))
    # HBM accounting intact after evict + re-stage
    check_budget(b.cache)


def test_batcher_concurrent_device_probe_coalesces_identically():
    """Concurrent searches over device-probed batches (the coalescer's
    fused dispatch) must serialize to the same bytes as solo runs."""
    from tempo_tpu.search.batcher import BlockBatcher

    blocks = _blocks(n=2, small_tail=False)
    jobs = _jobs(blocks)
    serial_b = BlockBatcher(coalesce_max_queries=1,
                            device_probe_min_vals=10)
    co_b = BlockBatcher(coalesce_window_s=0.05, coalesce_max_queries=4,
                        device_probe_min_vals=10)
    reqs = [_mk_req({"session.id": f"session-0{i:02d}"[:11]}, limit=200)
            for i in range(4)]
    serial = [serial_b.search(jobs, r).response().SerializeToString()
              for r in reqs]
    co_b.search(jobs, reqs[0])  # warm staging + compile
    barrier = threading.Barrier(len(reqs))
    got = [None] * len(reqs)

    def worker(i):
        barrier.wait()
        got[i] = co_b.search(jobs, reqs[i]).response().SerializeToString()

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(len(reqs))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert got == serial


# ---------------------------------------------------------------------------
# satellites: fingerprint from the encoded dictionary section, bisected
# tag-values


def test_dict_fingerprint_from_encoded_section():
    sd = SearchData(trace_id=b"\x01" * 16, start_s=1, end_s=2, dur_ms=5)
    sd.kvs = {"k1": {"v1", "v2"}, "k2": {"v3"}}
    pages = ColumnarPages.build([sd], PageGeometry(4, 8))
    blob = pages.to_bytes()
    p2 = ColumnarPages.from_bytes(blob)
    # the decoded container carries the build-time digest: the first
    # cache touch must not walk the dictionaries
    assert p2._dict_section_sha == pages._dict_section_sha
    import hashlib
    from unittest import mock

    with mock.patch.object(hashlib, "sha256",
                           side_effect=AssertionError("python walk ran")):
        fp = pipeline._dict_fingerprint(p2, p2.key_dict, p2.val_dict)
    assert fp == p2._dict_section_sha
    # all decodes of the same container share the fingerprint (compile
    # cache sharing across blocks with identical dictionaries)
    p3 = ColumnarPages.from_bytes(blob)
    assert pipeline._dict_fingerprint(p3, p3.key_dict, p3.val_dict) == fp
    # a page-range slice inherits it (no per-job rehash)
    assert p2.slice_pages(0, 1)._dict_section_sha == fp
    # synthetic/in-memory containers still walk (and still work)
    p4 = ColumnarPages.build([sd], PageGeometry(4, 8))
    assert pipeline._dict_fingerprint(p4, p4.key_dict, p4.val_dict)


def test_legacy_container_without_dict_sha_header():
    import json as _json
    import struct

    sd = SearchData(trace_id=b"\x02" * 16, start_s=1, end_s=2, dur_ms=5)
    sd.kvs = {"k": {"v"}}
    pages = ColumnarPages.build([sd], PageGeometry(4, 8))
    blob = pages.to_bytes()
    hdr_s = struct.Struct("<IIQ")
    magic, version, hdr_len = hdr_s.unpack_from(blob)
    hdr = _json.loads(blob[hdr_s.size:hdr_s.size + hdr_len])
    del hdr["dict_sha"]
    hdr_b = _json.dumps(hdr).encode()
    legacy = hdr_s.pack(magic, version, len(hdr_b)) + hdr_b \
        + blob[hdr_s.size + hdr_len:]
    p = ColumnarPages.from_bytes(legacy)
    # falls back to hashing the encoded section bytes — same digest
    assert p._dict_section_sha == pages._dict_section_sha


def test_values_for_key_bisect():
    sd = SearchData(trace_id=b"\x03" * 16, start_s=1, end_s=2, dur_ms=5)
    sd.kvs = {"bb": {"v1", "v2"}, "dd": {"v3"}}
    pages = ColumnarPages.build([sd], PageGeometry(4, 8))
    assert sorted(pages.values_for_key("bb")) == ["v1", "v2"]
    assert list(pages.values_for_key("dd")) == ["v3"]
    assert list(pages.values_for_key("aa")) == []  # before first key
    assert list(pages.values_for_key("cc")) == []  # between keys
    assert list(pages.values_for_key("zz")) == []  # past the end
