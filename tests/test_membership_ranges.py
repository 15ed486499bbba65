"""Membership by ranges where a needle's hits are runs of the sorted
dictionary (PR 33): the device probe yields, beside its hit mask, the
runs of the mask over the sorted ids, and a term of at most
`dict_probe.R_MAX` runs leaves the probe as `[lo, hi]` id ranges, a few
ints, exactly what the host path makes; its block is scanned by
compares and its launch takes no mask.

Held here, small and on the CPU: the three ways to test membership
agree on random dictionaries; a group that mixes blocks under and over
the device probe's floor, and launches that mix range and mask members,
answer as each alone; the breaker's host route overwrites a cached
device mask; the counters, gauges and span attributes say what
happened. The served path at a deployment's cardinality is
`test_highcard_served.py`.
"""

import random
import threading

import numpy as np
import pytest

from conftest import check_budget
from tempo_tpu import tempopb
from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import tracing
from tempo_tpu.search import dict_probe, pipeline
from tempo_tpu.search.batcher import BlockBatcher, ScanJob
from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
from tempo_tpu.search.data import SearchData
from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi
from tempo_tpu.search.pipeline import (
    compile_query, ids_to_ranges, substring_value_ids,
)

R_SMALL = 4     # the rule's constant, lowered so that small needles pass it


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.setattr(dict_probe, "R_MAX", R_SMALL)
    pipeline._COMPILE_CACHE.clear()
    yield
    pipeline._COMPILE_CACHE.clear()


def _req(tags=None, **kw):
    req = tempopb.SearchRequest()
    for k, v in (tags or {}).items():
        req.tags[k] = v
    for k, v in kw.items():
        setattr(req, k, v)
    return req


def _dictionary(seed: int, n: int = 400) -> list:
    """A sorted dictionary of ids that share a prefix, with clumps (a
    prefix is one run) and scattered letters (an infix is many)."""
    rng = random.Random(seed)
    return sorted({"cus_" + "".join(rng.choice("abcde") for _ in range(5))
                   for _ in range(n)} | {"svc-a", "svc-b", "zzz"})


def _ranges_of(mask_row) -> np.ndarray:
    return ids_to_ranges(np.flatnonzero(np.asarray(mask_row)).astype(np.int32))


NEEDLES = {
    "exact": lambda d, rng: rng.choice(d),
    "prefix": lambda d, rng: rng.choice(d)[:6],
    "infix-few-runs": lambda d, rng: rng.choice(d)[5:9],
    "infix-many-runs": lambda d, rng: rng.choice("abcde"),
    "absent": lambda d, rng: "cus_qqqqq",
    "empty": lambda d, rng: "",
}


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("kind", sorted(NEEDLES))
def test_device_ranges_equal_device_mask_equal_host(kind, seed):
    """One probe, three readings of it: the mask, the runs the same
    dispatch yields, and the host's id set."""
    vals = _dictionary(seed)
    needle = NEEDLES[kind](vals, random.Random(seed))
    ddev = dict_probe.place_device_dict(dict_probe.pack_device_dict(vals))
    hits, any_hits, n_runs, bounds = (
        np.asarray(a) for a in dict_probe.probe_values(
            ddev, [needle.encode()]))
    host = substring_value_ids(vals, needle)
    assert np.array_equal(np.flatnonzero(hits[0][:len(vals)]), host)
    assert bool(any_hits[0]) == bool(host.size)
    want = ids_to_ranges(host)
    assert n_runs[0] == len(want)
    kept = min(len(want), R_SMALL)
    assert np.array_equal(bounds[0, :kept], want[:kept])
    assert (bounds[0, kept:] == [1, 0]).all()     # matches nothing

    # and through the compile: ranges up to R_MAX runs, else the mask
    pages = _block(vals, seed)
    sd = next(iter(MultiBlockEngine(device_probe_min_vals=1).stage(
        [pages]).staged_dicts.values()))
    req = _req({"customer.id": needle})
    dev = compile_query(pages.key_dict, pages.val_dict, req, staged_dict=sd)
    hst = compile_query(pages.key_dict, pages.val_dict, req)
    if kind == "absent":
        assert dev is None and hst is None
        return
    if len(want) <= R_SMALL:
        assert dev.val_hits is None
        assert np.array_equal(dev.val_ranges, hst.val_ranges)
    else:
        assert np.array_equal(
            _ranges_of(np.asarray(dev.val_hits)[0]),
            hst.val_ranges[0][:len(want)])


@pytest.mark.parametrize("n", [1, 2, 512, 1024, 2048, 1 << 15])
def test_two_level_cumsum_is_cumsum(n):
    """The probe's scan over a dictionary's bytes is cut in rows for the
    compiler's sake (dict_probe._cumsum_pow2); the sums are the same."""
    import jax.numpy as jnp

    x = np.random.default_rng(n).integers(0, 2, n).astype(np.int32)
    assert np.array_equal(np.asarray(dict_probe._cumsum_pow2(jnp.asarray(x))),
                          np.cumsum(x))


def _block(vals: list, seed: int, entries: int = 96, extra=None):
    """A block whose dictionary is `vals`: every value on some entry."""
    rng = random.Random(seed)
    out = []
    ids = [v for v in vals if v.startswith("cus_")] + list(extra or [])
    for i in range(max(entries, len(ids))):
        tid = (seed.to_bytes(2, "big") + i.to_bytes(4, "big")).rjust(
            16, b"\x00")
        sd = SearchData(trace_id=tid)
        sd.start_s = 1_600_000_000 + seed * 100_000 + i
        sd.end_s = sd.start_s + 3
        sd.dur_ms = rng.randint(1, 20_000)
        sd.kvs = {"customer.id": {ids[i % len(ids)]},
                  "svc": {rng.choice(["svc-a", "svc-b"])},
                  "z": {"zzz"}}
        out.append(sd)
    return ColumnarPages.build(out, PageGeometry(32, 8))


def _group():
    """Three blocks over the floor the tests set (their dictionaries are
    probed on the device) and one under it (on the host)."""
    big = [_block(_dictionary(s), s) for s in (1, 2, 3)]
    small = _block(["cus_aaaaa", "cus_abcde", "svc-a", "svc-b", "zzz"], 9)
    return big + [small]


FLOOR = 50


def _jobs(blocks):
    return [ScanJob(
        key=(f"blk-{i:03d}", 0, p.n_pages), pages_fn=(lambda p=p: p),
        header=dict(p.header), n_pages=p.n_pages, n_entries=p.n_entries,
        geometry=(p.header["entries_per_page"], p.header["kv_per_entry"]))
        for i, p in enumerate(blocks)]


def _answers(batcher, jobs, reqs):
    return [batcher.search(jobs, r).response().SerializeToString()
            for r in reqs]


RANGE_REQS = [{"customer.id": "cus_a"}, {"customer.id": "cus_ab"},
              {"customer.id": "cus_abcde"}]
MASK_REQS = [{"customer.id": "a"}, {"customer.id": "e"}]


def _wide_needle(blocks) -> str:
    """An infix that is 2 to R_SMALL runs of some block's dictionary and
    no more of any: ranges, and more than one a term."""
    for a in "abcde":
        for b in "abcde":
            needle = a + b + "cd"
            runs = [len(ids_to_ranges(substring_value_ids(p.val_dict, needle)))
                    for p in blocks]
            if 2 <= max(runs) <= R_SMALL:
                return needle
    raise AssertionError("no such needle in these dictionaries")


def test_group_on_both_sides_of_the_floor_and_mixed_launches(monkeypatch):
    """A group with blocks on both sides of the floor, asked range and
    mask predicates at once: every answer is the host-only batcher's,
    no launch mixes the kinds (ranges, ranges wider than the
    coalescer's `WIDE_RANGES`, masks), and a range member's launch is
    given no `val_hits`. Counted from the launches of ITS batcher: a
    process-wide counter also holds what an earlier test's abandoned
    dispatch worker launches once its injected hang is slept out."""
    from tempo_tpu.search import coalescer as coalescer_mod

    monkeypatch.setattr(coalescer_mod, "WIDE_RANGES", 1)
    blocks = _group()
    jobs = _jobs(blocks)
    wide = [{"customer.id": _wide_needle(blocks)}]
    reqs = [_req(t, limit=500) for t in RANGE_REQS + wide + MASK_REQS]
    host = BlockBatcher(coalesce_max_queries=1, device_probe_min_vals=0)
    want = _answers(host, jobs, reqs)

    pipeline._COMPILE_CACHE.clear()
    solo = BlockBatcher(coalesce_max_queries=1, device_probe_min_vals=FLOOR)
    assert _answers(solo, jobs, reqs) == want
    (gkey, *_rest) = solo.cache.snapshot()["entries"]
    batch = solo.cache.resident(gkey).batch
    assert len(batch.staged_dicts) == 3        # the small block: host
    kinds = {}
    for t in RANGE_REQS + MASK_REQS:
        mq = compile_multi(blocks, _req(t), cache_on=batch)
        kinds[t["customer.id"]] = mq
        if t in MASK_REQS:
            assert (mq.block_group[:3] >= 0).all()
            assert mq.block_group[3] == -1      # its ranges apply
    assert all(kinds[t["customer.id"]].val_hits is None for t in RANGE_REQS)

    pipeline._COMPILE_CACHE.clear()
    co = BlockBatcher(coalesce_window_s=0.2, coalesce_max_queries=8,
                      device_probe_min_vals=FLOOR)
    assert _answers(co, jobs, reqs) == want     # warm: stage + compile
    launches = []
    real = co.engine._launch

    def spy(mode, batch, q, place, **kw):
        tables, *_ = place()
        launches.append((mode, kw.get("queries", 1), tables[7] is not None,
                         q.val_hits is not None, q.val_ranges.shape[-2]))
        return real(mode, batch, q, place, **kw)

    co.engine._launch = spy
    barrier = threading.Barrier(len(reqs))
    got = [None] * len(reqs)

    def worker(i):
        barrier.wait()
        got[i] = co.search(jobs, reqs[i]).response().SerializeToString()

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(len(reqs))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert got == want
    # a launch's members are all of one kind, and the operand follows it
    assert all(given == member for _m, _n, given, member, _r in launches)
    assert sum(n for _m, n, given, _, r in launches
               if not given and r == 1) == len(RANGE_REQS)
    assert sum(n for _m, n, given, _, r in launches
               if not given and r > 1) == len(wide)
    assert sum(n for _m, n, given, *_ in launches if given) == len(MASK_REQS)


@pytest.mark.parametrize("route", ["host_only", "breaker_open"])
def test_host_route_answers_alike_and_overwrites_a_cached_mask(route):
    """`host_only` and an open breaker never read a device mask: the
    answers are the device path's, and the cached device product is
    overwritten by the host's ranges."""
    from conftest import scan_batch
    from tempo_tpu.robustness import BREAKER

    blocks = _group()[:3]
    req = _req(MASK_REQS[0], limit=500)
    dev = scan_batch(blocks, req, top_k=512, probe_min_vals=FLOOR)
    assert dev.mq.val_hits is not None
    fp = pipeline._dict_fingerprint(blocks[0], blocks[0].key_dict,
                                    blocks[0].val_dict)
    sig = pipeline._tags_sig(req)
    assert pipeline._COMPILE_CACHE[fp][sig][3] is not None
    pinned = obs.probe_mask_bytes.value(held_by="probe_cache")
    assert pinned >= 3 * dev.mq.val_hits.shape[2]

    if route == "host_only":
        again = scan_batch(blocks, req, top_k=512, host_only=True)
        assert again.mq.val_hits is None
    else:
        was = BREAKER.enabled, BREAKER._state
        BREAKER.enabled, BREAKER._state = True, "open"
        try:
            mq = compile_multi(blocks, req, cache_on=dev.batch)
        finally:
            BREAKER.enabled, BREAKER._state = was
        assert mq.val_hits is None
        again = scan_batch(blocks, req, top_k=512, host_only=True)
    assert again.out[:2] == dev.out[:2]
    assert ([m.trace_id for m in again.metas]
            == [m.trace_id for m in dev.metas])
    # overwritten: the cache now serves ranges, and the masks' HBM is
    # given back
    assert pipeline._COMPILE_CACHE[fp][sig][3] is None
    assert obs.probe_mask_bytes.value(held_by="probe_cache") < pinned


def test_counters_gauges_and_span_attributes_say_what_happened():
    """Two searches over one group of three device-probed blocks, one
    predicate that leaves the probe as ranges and one as masks: three
    device probes each and none on the host, then the compile cache;
    one launch member each by membership; the memo's mask stack is
    charged to its batch and published; the spans carry it all."""
    blocks = _group()[:3]
    jobs = _jobs(blocks)
    collector = tracing.CollectExporter()
    tracing.set_tracer(tracing.Tracer(tracing.SyncProcessor(collector)))
    try:
        b = BlockBatcher(coalesce_max_queries=1, device_probe_min_vals=FLOOR)
        before = {p: obs.dict_probes.value(path=p)
                  for p in ("device", "host", "cached")}
        members = {p: obs.scan_membership.value(path=p)
                   for p in ("range", "mask")}
        memo_before = obs.probe_mask_bytes.value(held_by="memo")
        with tracing.start_span("test.root"):
            b.search(jobs, _req(RANGE_REQS[0], limit=50))
            b.search(jobs, _req(MASK_REQS[0], limit=50))
            b.search(jobs, _req(MASK_REQS[0], limit=50))      # memo hit
            b.search(jobs, _req(MASK_REQS[0], min_duration_ms=5,
                                limit=50))      # memo miss, cache hit
    finally:
        tracing.set_tracer(None)
    moved = {p: obs.dict_probes.value(path=p) - before[p] for p in before}
    assert moved == {"device": 6, "host": 0, "cached": 3}
    assert {p: obs.scan_membership.value(path=p) - members[p]
            for p in members} == {"range": 1, "mask": 3}

    (gkey,) = b.cache.snapshot()["entries"]
    entry = b.cache.resident(gkey)
    stack = 3 * 1 * next(iter(entry.batch.staged_dicts.values())).v_pad
    assert entry.mask_bytes == 2 * stack        # two predicates' stacks
    assert obs.probe_mask_bytes.value(held_by="memo") - memo_before \
        == 2 * stack
    assert check_budget(b.cache)[gkey][2] == entry.mask_bytes
    assert entry.nbytes >= int(entry.batch.nbytes) + 2 * stack
    assert obs.probe_mask_peak_bytes.value() >= 2 * stack

    spans = {s.context.span_id: s for s in collector.spans}
    probes = [s for s in collector.spans if s.name == "dict_probe.probe"]
    assert [dict(s.attributes)["path"] for s in probes] == [
        "device", "device", "cached"]
    assert [dict(s.attributes)["membership"] for s in probes] == [
        "range", "mask", "mask"]
    first = dict(probes[0].attributes)
    assert first["terms"] == 1 and first["dicts"] == 3
    assert first["device"] == 3 and first["runs_max"] == 1
    for s in probes:
        parent = spans[s.parent_span_id]
        assert parent.name == "batcher.prepare"
        assert parent.start_ns <= s.start_ns <= s.end_ns <= parent.end_ns
    # a shape's first launch books its stage as `compile`
    launched = [dict(s.attributes).get("membership")
                for s in collector.spans
                if s.name in ("dispatch.execute", "dispatch.compile")
                and dict(s.attributes).get("mode") == "batched"]
    assert sorted(launched) == ["mask", "mask", "mask", "range"]
    # dropping the batch gives the memo's masks back
    with b.cache.group_lock:
        b.cache._drop_hbm_locked(gkey)
    assert obs.probe_mask_bytes.value(held_by="memo") == memo_before


# ---- PR 36: from multiblock.ENTRY_RANGES ranges a term on, the compares
# test an entry's value for the term's key, not every slot of the entry ----

C_SLOTS = 8
WIDTHS = (1, 8, 16, 64, 128, 512, 1024)
MULTIPLICITIES = (0, 1, 2, 5, C_SLOTS)   # values an entry has for the key


def _slot_formula(kk, vv, valid, page_block, term_keys, val_ranges,
                  term_active=None):
    """The predicate as it was before the entry form, in numpy: every
    slot against every range, then the slot's key. The reference the
    kernel's two range forms are held to."""
    safe = np.maximum(page_block, 0)
    mask = valid & (page_block >= 0)[:, None]
    for t in range(term_keys.shape[1]):
        if term_active is not None and not term_active[t]:
            continue
        keym = kk == term_keys[safe, t][:, None, None]
        lo = val_ranges[safe, t, :, 0][:, None, None, :]
        hi = val_ranges[safe, t, :, 1][:, None, None, :]
        v = vv.astype(np.int64)[..., None]
        mask = mask & (keym & ((v >= lo) & (v <= hi)).any(-1)).any(-1)
    return mask


def _set_oracle(kk, vv, valid, page_block, term_keys, val_ranges):
    """The same answer without arrays: an entry's set of values for the
    term's key against the block's ranges, one entry at a time."""
    out = np.zeros(valid.shape, dtype=bool)
    for p, e in zip(*np.nonzero(valid)):
        b = page_block[p]
        if b < 0:
            continue
        out[p, e] = all(
            any(lo <= v <= hi for v in
                {int(v) for k, v in zip(kk[p, e], vv[p, e])
                 if k == term_keys[b, t]}
                for lo, hi in val_ranges[b, t])
            for t in range(term_keys.shape[1]))
    return out


def _synthetic(mult: int, R: int, T: int, seed: int):
    """Six pages of three blocks whose every valid entry has `mult`
    values for key 3 (slots 1 .. mult, as ColumnarPages.build lays a
    multi-valued key: adjacent, in sorted-key order) and, while a slot
    is left, one for key 9; trailing slots are pads (-1, -1). Block 0's
    ranges hold value id 0, block 1's are sentinels past their first
    half, block 2 has the -1 key sentinel for term 0."""
    rng = np.random.default_rng(seed)
    P, E, B, V = 6, 16, 3, 4000
    kk = np.full((P, E, C_SLOTS), -1, dtype=np.int8)
    vv = np.full((P, E, C_SLOTS), -1, dtype=np.int16)
    kk[..., 0] = 1
    kk[..., 1:1 + mult] = 3
    used = min(C_SLOTS, 1 + mult)
    if used < C_SLOTS:
        kk[..., used] = 9
        used += 1
    more = rng.integers(used, C_SLOTS + 1, (P, E))      # filled slots
    for c in range(used, C_SLOTS):
        kk[..., c] = np.where(more > c, 20 + c, -1)
    vv[kk >= 0] = rng.integers(0, V, int((kk >= 0).sum()))
    vv[0, :4, 1:1 + mult] = 0                            # value id 0
    valid = rng.random((P, E)) < 0.9
    kk[~valid] = -1
    vv[~valid] = -1
    page_block = np.repeat(np.arange(B, dtype=np.int32), P // B)
    term_keys = np.tile(np.array([3, 9][:T], dtype=np.int32), (B, 1))
    term_keys[2, 0] = -1
    lo = np.sort(rng.integers(0, V, (B, T, R)), axis=-1)
    lo[0, :, 0] = 0
    val_ranges = np.stack(
        [lo, lo + rng.integers(0, 3, lo.shape)], -1).astype(np.int32)
    val_ranges[1, :, (R + 1) // 2:] = [1, 0]
    return kk, vv, valid, page_block, term_keys, val_ranges


@pytest.mark.parametrize("T", [1, 2])
@pytest.mark.parametrize("R", WIDTHS)
@pytest.mark.parametrize("mult", MULTIPLICITIES)
def test_both_range_forms_equal_the_slot_formula_and_the_oracle(mult, R, T):
    """multi_entry_mask at every width on both sides of ENTRY_RANGES,
    for entries with no, one, several and C values of the term's key."""
    import functools

    import jax
    import jax.numpy as jnp

    from tempo_tpu.search.multiblock import multi_entry_mask

    kk, vv, valid, page_block, term_keys, val_ranges = _synthetic(
        mult, R, T, seed=R + mult)
    want = _slot_formula(kk, vv, valid, page_block, term_keys, val_ranges)
    assert np.array_equal(
        want, _set_oracle(kk, vv, valid, page_block, term_keys, val_ranges))
    if mult and T == 1:
        assert want[:2].any() and not want[4:].any()  # id 0; the -1 key
    ones = jnp.ones(valid.shape, dtype=jnp.uint32)
    got = jax.jit(functools.partial(multi_entry_mask, n_terms=T))(
        jnp.asarray(kk), jnp.asarray(vv), ones, ones, ones,
        jnp.asarray(valid), jnp.asarray(page_block), jnp.asarray(term_keys),
        jnp.asarray(val_ranges), jnp.uint32(0), jnp.uint32(0xFFFFFFFF),
        jnp.uint32(0), jnp.uint32(0xFFFFFFFF))
    assert np.array_equal(np.asarray(got), want)


MARKS = {1: 1, 8: 6, 16: 12, 64: 40, 128: 100, 512: 300, 1024: 600}
LAUNCHES = ("solo", "fused2", "fused4", "mesh_solo", "mesh_fused")


def _marked(n: int) -> str:
    """Value n of a pool that sorts by n. An even n under 2r carries the
    marker `-m<r>.`, the odd ones between them none: the needle `-m<r>.`
    hits r values, each a run of its own in the sorted dictionary, so
    its ranges pad to the width MARKS names it for; n = 0 has them all."""
    return f"id{n:04d}" + "".join(
        f"-m{r}." for r in MARKS.values() if n % 2 == 0 and n // 2 < r)


def _multivalued_block(seed: int, pool: int, with_key: bool = True):
    """(entries, pages): entries take the pool's values in order, 0, 1,
    2, 5, C - 1 and C at a time for `customer.id` (the C-valued ones
    have no slot left for another key), until every value is on some
    entry, so the dictionary is the pool."""
    rng = random.Random(seed)
    entries, n = [], 0
    while n < pool or len(entries) % 6:
        take = (0, 1, 2, 5, C_SLOTS - 1, C_SLOTS)[len(entries) % 6]
        tid = (seed.to_bytes(2, "big") + len(entries).to_bytes(4, "big")
               ).rjust(16, b"\x00")
        sd = SearchData(trace_id=tid)
        sd.start_s = 1_600_000_000 + seed * 100_000 + len(entries)
        sd.end_s = sd.start_s + 3
        sd.dur_ms = rng.randint(1, 20_000)
        sd.kvs = {}
        if with_key and take:
            sd.kvs["customer.id"] = {_marked((n + i) % pool)
                                     for i in range(take)}
            n += take
        if take < C_SLOTS:
            sd.kvs["svc"] = {rng.choice(["svc-a", "svc-b"])}
        entries.append(sd)
        if not with_key and len(entries) >= 60:
            break
    return entries, ColumnarPages.build(entries, PageGeometry(32, C_SLOTS))


@pytest.fixture(scope="module")
def multivalued():
    """Two blocks whose dictionaries hold the whole pool (1,300 values,
    value id 0 a hit of every needle), and one without the key at all:
    its term key is the -1 sentinel."""
    built = [_multivalued_block(1, 1300), _multivalued_block(2, 1300),
             _multivalued_block(3, 0, with_key=False)]
    return ([sd for entries, _ in built for sd in entries],
            [pages for _, pages in built])


def _width_reqs(R: int) -> list:
    needle = f"-m{MARKS[R]}."
    reqs = [_req({"customer.id": needle}),
            _req({"customer.id": needle, "svc": "svc-a"}),
            _req({"customer.id": needle, "svc": "svc-b"},
                 min_duration_ms=4_000),
            _req({"customer.id": needle}, max_duration_ms=15_000)]
    for r in reqs:
        r.limit = 1000
    return reqs


def _launch(eng, batch, mqs, fused: bool, top_k: int) -> list:
    """[(count, inspected, scores, idx)] a member: each alone, or all
    in one fused launch."""
    from tempo_tpu.search.engine import fetch_scan_out
    from tempo_tpu.search.multiblock import stack_queries

    if not fused:
        return [eng.scan(batch, mq) for mq in mqs]
    cq = stack_queries(mqs)
    if len({mq.n_terms for mq in mqs}) > 1:
        # the one-term member rides with its second term inactive
        assert cq.n_terms == 2 and not cq.term_active[0, 1]
    counts, inspected, scores, idx = fetch_scan_out(
        eng.coalesced_scan_async(batch, cq, top_k))
    return [(int(counts[i]), inspected, scores[i], idx[i])
            for i in range(len(mqs))]


def _check_launches(launch, R, entries, blocks, packed=False):
    """Every request of width R through one launch kind: count and
    matches equal the per-entry oracle's and the slot formula's over the
    staged columns."""
    from tempo_tpu.parallel import make_mesh
    from tempo_tpu.search import packing
    from tempo_tpu.search.data import search_data_matches

    top_k = 1024
    eng = MultiBlockEngine(
        top_k=top_k, mesh=make_mesh(8) if launch.startswith("mesh") else None)
    batch = eng.stage(blocks)
    assert (batch.widths is not None) == packed
    reqs = _width_reqs(R)
    mqs = [compile_multi(blocks, r, cache_on=batch) for r in reqs]
    assert all(mq.val_hits is None and mq.val_ranges.shape[2] == R
               for mq in mqs)
    assert (mqs[0].term_keys[2] == -1).all()
    n = 4 if launch in ("fused4", "mesh_fused") else 2
    mqs, reqs = mqs[:n], reqs[:n]
    outs = _launch(eng, batch, mqs, "fused" in launch, top_k)
    d = {k: np.asarray(v) for k, v in batch.device.items()}
    kw, vw, _ = batch.widths or (None, None, None)
    kk = np.asarray(packing.unpack_ids(batch.device["kv_key"], kw))
    vv = np.asarray(packing.unpack_ids(batch.device["kv_val"], vw))
    for req, mq, (count, _inspected, scores, idx) in zip(reqs, mqs, outs):
        expected = {sd.trace_id for sd in entries
                    if search_data_matches(sd, req)}
        assert len(expected) > 1 or R == 1
        assert count == len(expected)
        ids = {bytes.fromhex(m.trace_id)
               for m in eng.results(batch, mq, scores, idx)}
        assert ids == expected
        by_slot = _slot_formula(kk, vv, d["entry_valid"], d["page_block"],
                                mq.term_keys, mq.val_ranges)
        dur = d["entry_dur"].astype(np.int64)
        by_slot &= (dur >= mq.dur_lo) & (dur <= mq.dur_hi)
        assert set(np.flatnonzero(by_slot).tolist()) == set(
            idx[scores >= 0].tolist())


@pytest.mark.parametrize("R", WIDTHS)
@pytest.mark.parametrize("launch", LAUNCHES)
def test_launch_kinds_answer_as_the_oracle_at_every_width(
        launch, R, multivalued):
    """Solo, fused (two and four members, one with an inactive padded
    term), and both on a mesh of the host's eight devices: entries with
    0, 1, 2, 5, C - 1 and C values of the term's key, value id 0, pads,
    a block whose term key is the -1 sentinel, ranges that are
    sentinels past a block's own runs."""
    _check_launches(launch, R, *multivalued)


@pytest.mark.parametrize("R", [8, 16, 512])
@pytest.mark.parametrize("launch", ["solo", "fused4", "mesh_fused"])
def test_packed_widths_answer_alike(launch, R, multivalued):
    """The same under packed residency: the unpack runs inside the term
    body, before either range form."""
    from tempo_tpu.search import packing

    packing.configure(enabled=True)
    try:
        _check_launches(launch, R, *multivalued, packed=True)
    finally:
        packing.configure(enabled=False)


@pytest.mark.parametrize("R", [16, 64])
@pytest.mark.parametrize("launch", ["solo", "fused2"])
def test_a_mask_group_beside_wide_host_ranges(launch, R, multivalued):
    """One launch, both arms of the kernel's `where(probe_page, ...)`
    in the entry form: a block over the floor whose needle passes R_MAX
    leaves the probe as a hit mask, a block under it is compiled on the
    host to R ranges (multi-valued key and all)."""
    from tempo_tpu.search.data import search_data_matches
    from tempo_tpu.search.multiblock import ENTRY_RANGES

    assert R >= ENTRY_RANGES
    big_entries = [sd for sd in multivalued[0]
                   if sd.trace_id[10:12] == (1).to_bytes(2, "big")]
    small_entries, small = _multivalued_block(7, 2 * MARKS[R])
    entries, blocks = big_entries + small_entries, [multivalued[1][0], small]
    eng = MultiBlockEngine(top_k=1024, device_probe_min_vals=500)
    batch = eng.stage(blocks)
    assert len(batch.staged_dicts) == 1
    reqs = _width_reqs(R)[:2]
    mqs = [compile_multi(blocks, r, cache_on=batch) for r in reqs]
    for mq in mqs:
        assert mq.val_hits is not None and mq.val_ranges.shape[2] == R
        assert mq.block_group.tolist() == [0, -1]
    outs = _launch(eng, batch, mqs, launch != "solo", 1024)
    for req, mq, (count, _inspected, scores, idx) in zip(reqs, mqs, outs):
        expected = {sd.trace_id for sd in entries
                    if search_data_matches(sd, req)}
        in_small = {sd.trace_id for sd in small_entries} & expected
        assert in_small and expected - in_small
        assert count == len(expected)
        assert {bytes.fromhex(m.trace_id) for m in eng.results(
            batch, mq, scores, idx)} == expected


def test_counter_and_span_say_which_range_form_a_launch_traced(multivalued):
    """`tempo_search_scan_range_compare_total{by}` and `compare` on the
    launch's span follow the launch's R alone; the membership counter
    counts as it did."""
    from tempo_tpu.search.multiblock import ENTRY_RANGES

    blocks = multivalued[1]
    jobs = _jobs(blocks)
    under, at = max(r for r in WIDTHS if r < ENTRY_RANGES), ENTRY_RANGES
    collector = tracing.CollectExporter()
    tracing.set_tracer(tracing.Tracer(tracing.SyncProcessor(collector)))
    try:
        b = BlockBatcher(coalesce_max_queries=1, device_probe_min_vals=0)
        before = {k: obs.scan_range_compare.value(by=k)
                  for k in ("slot", "entry")}
        members = {p: obs.scan_membership.value(path=p)
                   for p in ("range", "mask")}
        with tracing.start_span("test.root"):
            for R in (1, under, at, 512):
                b.search(jobs, _width_reqs(R)[0])
    finally:
        tracing.set_tracer(None)
    assert {k: obs.scan_range_compare.value(by=k) - before[k]
            for k in before} == {"slot": 2, "entry": 2}
    assert {p: obs.scan_membership.value(path=p) - members[p]
            for p in members} == {"range": 4, "mask": 0}
    launched = [dict(s.attributes) for s in collector.spans
                if s.name in ("dispatch.execute", "dispatch.compile")
                and dict(s.attributes).get("mode") == "batched"]
    assert [a["compare"] for a in launched] == [
        "slot", "slot", "entry", "entry"]
    assert {a["membership"] for a in launched} == {"range"}
