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
    no launch mixes the kinds (ranges, ranges wider than the batcher's
    `_WIDE_RANGES`, masks), and a range member's launch is given no
    `val_hits`."""
    from tempo_tpu.search import batcher as batcher_mod

    monkeypatch.setattr(batcher_mod, "WIDE_RANGES", 1)
    blocks = _group()
    jobs = _jobs(blocks)
    wide = [{"customer.id": _wide_needle(blocks)}]
    reqs = [_req(t, limit=500) for t in RANGE_REQS + wide + MASK_REQS]
    host = BlockBatcher(coalesce_max_queries=1, device_probe_min_vals=0)
    want = _answers(host, jobs, reqs)

    pipeline._COMPILE_CACHE.clear()
    solo = BlockBatcher(coalesce_max_queries=1, device_probe_min_vals=FLOOR)
    assert _answers(solo, jobs, reqs) == want
    batch = next(iter(solo._cache.values())).batch
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
        tables, _resident = place()
        launches.append((mode, kw.get("queries", 1), tables[7] is not None,
                         q.val_hits is not None, q.val_ranges.shape[-2]))
        return real(mode, batch, q, place, **kw)

    co.engine._launch = spy
    range_before = obs.scan_membership.value(path="range")
    mask_before = obs.scan_membership.value(path="mask")
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
    assert obs.scan_membership.value(path="range") - range_before == len(
        RANGE_REQS + wide)
    assert obs.scan_membership.value(path="mask") - mask_before == len(
        MASK_REQS)


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

    entry = next(iter(b._cache.values()))
    stack = 3 * 1 * next(iter(entry.batch.staged_dicts.values())).v_pad
    assert entry.mask_bytes == 2 * stack        # two predicates' stacks
    assert obs.probe_mask_bytes.value(held_by="memo") - memo_before \
        == 2 * stack
    assert b._cache_total == sum(e.nbytes for e in b._cache.values())
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
    with b._lock:
        b._drop_hbm_locked(next(iter(b._cache)))
    assert obs.probe_mask_bytes.value(held_by="memo") == memo_before
