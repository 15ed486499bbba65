import os
import random

import numpy as np
import pytest

from tempo_tpu import tempopb
from tempo_tpu.backend import BlockMeta, MockBackend
from tempo_tpu.model.matches import matches
from tempo_tpu.search import (
    BackendSearchBlock,
    ColumnarPages,
    PageGeometry,
    SearchResults,
    StreamingSearchBlock,
    decode_search_data,
    encode_search_data,
    extract_search_data,
    write_search_block,
)
from tempo_tpu.search.data import SearchData, search_data_matches

from tempo_tpu.search.pipeline import compile_query, substring_value_ids
from tempo_tpu.utils.ids import random_trace_id
from tempo_tpu.utils.test_data import make_trace

from conftest import scan_batch


def _mk_req(tags=None, **kw):
    req = tempopb.SearchRequest()
    for k, v in (tags or {}).items():
        req.tags[k] = v
    for k, v in kw.items():
        setattr(req, k, v)
    return req


def _corpus(n=500, seed=0):
    rng = random.Random(seed)
    entries = []
    for i in range(n):
        tid = bytes([i % 256, i // 256]) + os.urandom(14)
        sd = SearchData(trace_id=tid.rjust(16, b"\x00")[-16:])
        sd.start_s = 1_600_000_000 + i
        sd.end_s = sd.start_s + rng.randint(0, 10)
        sd.dur_ms = rng.randint(1, 30_000)
        sd.root_service = rng.choice(["frontend", "checkout", "cart"])
        sd.root_name = "GET /"
        sd.kvs = {
            "service.name": {sd.root_service},
            "http.status_code": {str(rng.choice([200, 404, 500]))},
            "region": {rng.choice(["us-east-1", "us-west-2", "eu-west-1"])},
        }
        entries.append(sd)
    return entries


def test_search_data_codec_roundtrip():
    sd = _corpus(3)[1]
    sd2 = decode_search_data(encode_search_data(sd), sd.trace_id)
    assert sd2.start_s == sd.start_s and sd2.end_s == sd.end_s
    assert sd2.dur_ms == sd.dur_ms
    assert sd2.root_service == sd.root_service
    assert sd2.kvs == sd.kvs


def test_extract_search_data_matches_proto_oracle():
    """Extracted search data must agree with the proto-level matcher for
    tag queries (the device kernel's semantics are defined by this)."""
    for seed in range(10):
        tid = random_trace_id()
        tr = make_trace(tid, seed=seed)
        sd = extract_search_data(tid, tr)
        for req in [
            _mk_req({"component": "grpc"}),
            _mk_req({"component": "db"}),
            _mk_req({"service.name": "check"}),
            _mk_req({"http.status_code": "500"}),
            _mk_req({"nonexistent": "x"}),
        ]:
            assert search_data_matches(sd, req) == matches(tr, req), (seed, req)


def test_end_before_start_duration_clamps_to_zero():
    """ADVICE r5 medium: a span with end < start (clock skew — valid
    client input) must yield dur_ms 0 on every extraction path, not a
    negative duration that struct.error-crashes encode_search_data
    (which surfaced as HTTP 500 on push, permanently failing on retry).
    The shared convention is max(0, end - start), matching the native
    walker's clamp."""
    from tempo_tpu.modules.distributor import Distributor
    from tempo_tpu.search.data import extract_search_data
    from tempo_tpu.utils.ids import random_trace_id

    tid = random_trace_id()
    b = tempopb.ResourceSpans()
    kv = b.resource.attributes.add()
    kv.key = "service.name"
    kv.value.string_value = "skewed"
    sp = b.scope_spans.add().spans.add()
    sp.trace_id = tid
    sp.name = "op"
    sp.start_time_unix_nano = 5_000_000_000
    sp.end_time_unix_nano = 2_000_000_000  # ends "before" it starts

    trace = tempopb.Trace()
    trace.batches.append(b)
    sd = extract_search_data(tid, trace)
    assert sd.dur_ms == 0
    encode_search_data(sd)  # used to raise struct.error

    by_trace, n, sds = Distributor._regroup_extract([b], 1 << 20)
    assert n == 1
    (sd2,) = sds.values()
    assert sd2.dur_ms == 0
    encode_search_data(sd2)  # used to raise struct.error


def test_substring_value_ids():
    vd = ["alpha", "beta", "alphabet", "gamma"]
    assert substring_value_ids(vd, "alpha").tolist() == [0, 2]
    assert substring_value_ids(vd, "bet").tolist() == [1, 2]
    assert substring_value_ids(vd, "zzz").size == 0
    assert substring_value_ids(vd, "").size == 4


def test_columnar_roundtrip():
    entries = _corpus(300)
    pages = ColumnarPages.build(entries, PageGeometry(entries_per_page=64, kv_per_entry=8))
    assert pages.n_entries == 300
    assert pages.n_pages >= 300 // 64
    blob = pages.to_bytes()
    p2 = ColumnarPages.from_bytes(blob)
    assert p2.n_entries == 300
    np.testing.assert_array_equal(p2.kv_key, pages.kv_key)
    np.testing.assert_array_equal(p2.trace_ids, pages.trace_ids)
    assert p2.key_dict == pages.key_dict
    assert p2.val_dict == pages.val_dict
    assert p2.header["max_end_s"] == pages.header["max_end_s"]


QUERIES = [
    _mk_req({"service.name": "frontend"}),
    _mk_req({"service.name": "front"}),                     # substring
    _mk_req({"service.name": "frontend", "http.status_code": "500"}),
    _mk_req({"region": "us"}),                              # multi-value substring
    _mk_req({}, min_duration_ms=10_000),
    _mk_req({}, max_duration_ms=500),
    _mk_req({"service.name": "cart"}, min_duration_ms=5_000, max_duration_ms=25_000),
    _mk_req({}, start=1_600_000_100, end=1_600_000_200),
    _mk_req({"http.status_code": "404"}, start=1_600_000_050, end=1_600_000_400),
    _mk_req({"service.name": "zzz-absent"}),
]


@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_engine_matches_host_oracle(qi):
    """The jit kernel must agree exactly with the host predicate."""
    req = QUERIES[qi]
    req.limit = 1000
    entries = _corpus(500)
    pages = ColumnarPages.build(entries, PageGeometry(64, 8))
    expected = {sd.trace_id for sd in entries if search_data_matches(sd, req)}

    got = scan_batch([pages], req, top_k=1024)
    if got.mq is None:
        assert not expected
        return
    assert got.count == len(expected)
    assert got.inspected == 500
    assert got.trace_ids == expected


def test_engine_topk_ordering_and_limit():
    entries = _corpus(500)
    pages = ColumnarPages.build(entries, PageGeometry(64, 8))
    req = _mk_req({"service.name": "frontend"})
    req.limit = 5
    metas = scan_batch([pages], req, top_k=128).metas
    assert len(metas) == 5
    starts = [m.start_time_unix_nano for m in metas]
    assert starts == sorted(starts, reverse=True)  # most recent first


def test_backend_search_block_end_to_end():
    be = MockBackend()
    meta = BlockMeta(tenant_id="t1")
    entries = _corpus(400)
    hdr = write_search_block(be, meta, entries, PageGeometry(64, 8))
    assert hdr["n_entries"] == 400

    from tempo_tpu.search.batcher import BlockBatcher

    batcher = BlockBatcher()
    jobs = [BackendSearchBlock(be, meta).scan_job()]
    req = _mk_req({"service.name": "checkout"})
    req.limit = 10
    res = batcher.search(jobs, req)
    resp = res.response()
    assert 0 < len(resp.traces) <= 10
    assert resp.metrics.inspected_blocks == 1
    assert resp.metrics.inspected_traces == 400
    for m in resp.traces:
        assert m.root_service_name == "checkout"

    # pruned by dictionary prefilter: absent key never touches the device
    res2 = batcher.search(jobs, _mk_req({"absent.key": "x"}))
    assert res2.metrics.skipped_blocks == 1

    # pruned by header time range
    res3 = batcher.search(
        jobs, _mk_req({}, start=1_700_000_000, end=1_700_000_100))
    assert res3.metrics.skipped_blocks == 1


def test_streaming_search_block_append_scan_replay(tmp_path):
    path = str(tmp_path / "head.search")
    ssb = StreamingSearchBlock(path)
    entries = _corpus(50)
    for sd in entries:
        ssb.append(sd.trace_id, sd)
    assert len(ssb) == 50

    req = _mk_req({"service.name": "frontend"})
    req.limit = 100
    res = SearchResults(limit=100)
    ssb.search(req, res)
    expected = sum(1 for sd in entries if search_data_matches(sd, req))
    assert len(res.response().traces) == expected
    ssb.close()

    # crash replay with torn tail
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) - 5)
    ssb2 = StreamingSearchBlock.rescan(path)
    assert len(ssb2) == 49
    # entries() sorted by trace id, feeds columnar build
    ids = [sd.trace_id for sd in ssb2.entries()]
    assert ids == sorted(ids)
    ssb2.clear()
    assert not os.path.exists(path)


def test_results_dedupe_and_sort():
    res = SearchResults(limit=10)
    m1 = tempopb.TraceSearchMetadata(trace_id="aa", start_time_unix_nano=5, duration_ms=10)
    m2 = tempopb.TraceSearchMetadata(trace_id="aa", start_time_unix_nano=3, duration_ms=20)
    m3 = tempopb.TraceSearchMetadata(trace_id="bb", start_time_unix_nano=9)
    for m in (m1, m2, m3):
        res.add(m)
    resp = res.response()
    assert len(resp.traces) == 2
    assert resp.traces[0].trace_id == "bb"  # most recent first
    aa = resp.traces[1]
    assert aa.start_time_unix_nano == 3 and aa.duration_ms == 20


def test_engine_limit_above_default_topk():
    """Requesting more results than the engine's default top_k must not
    silently truncate (regression: results were capped at top_k=128)."""
    entries = _corpus(500)  # ~1/3 match frontend
    pages = ColumnarPages.build(entries, PageGeometry(64, 8))
    req = _mk_req({"service.name": "frontend"})
    req.limit = 400
    got = scan_batch([pages], req, top_k=16)  # deliberately tiny default
    assert len(got.metas) == got.count  # every match surfaced, not 16


def test_columnar_adaptive_kv_capacity():
    """Build sizes C to the widest entry (pow2), capped by geometry;
    regression: a fixed small C silently dropped searchable tags."""
    wide = SearchData(trace_id=b"\x01" * 16, start_s=1, end_s=2, dur_ms=5)
    wide.kvs = {f"k{i}": {f"v{i}"} for i in range(11)}
    pages = ColumnarPages.build([wide], PageGeometry(entries_per_page=4))
    assert pages.geometry.kv_per_entry == 16  # next pow2 of 11
    assert pages.header["truncated_entries"] == 0
    req = _mk_req({"k10": "v10"})
    assert scan_batch([pages], req).count == 1
    # cap still enforced
    pages2 = ColumnarPages.build([wide], PageGeometry(4, 8))
    assert pages2.geometry.kv_per_entry == 8
    assert pages2.header["truncated_entries"] == 1


def test_native_substr_scan_matches_numpy():
    from tempo_tpu.ops import native
    from tempo_tpu.search.pipeline import pack_val_dict
    if not native.available():
        pytest.skip("native lib unavailable")
    vd = sorted({f"val-{i:06d}-{'x' if i % 3 else 'special'}" for i in range(10_000)})
    buf, offsets = pack_val_dict(vd)
    for needle in ("special", "val-0001", "zzz", "", "-x"):
        got = native.substr_scan(buf, offsets, needle.encode()).tolist()
        arr = np.array(vd, dtype=np.str_)
        want = np.nonzero(np.char.find(arr, needle) >= 0)[0].tolist()
        assert got == want, needle


def test_multiblock_scan_matches_per_block():
    from tempo_tpu.search.multiblock import (
        MultiBlockEngine, compile_multi, stack_blocks,
    )

    corpora = [_corpus(120, seed=s) for s in range(4)]
    blocks = [
        ColumnarPages.build(entries, PageGeometry(32, 8))
        for entries in corpora
    ]
    req = _mk_req({"service.name": "frontend"})
    req.limit = 1000
    mq = compile_multi(blocks, req)
    assert mq is not None
    batch = stack_blocks(blocks, pad_to=32)
    eng = MultiBlockEngine(top_k=1024)
    count, inspected, scores, idx = eng.scan(batch, mq)

    expected = {
        sd.trace_id
        for entries in corpora for sd in entries
        if search_data_matches(sd, req)
    }
    assert inspected == 480
    assert count == len(expected)
    got = {bytes.fromhex(m.trace_id) for m in eng.results(batch, mq, scores, idx)}
    assert got == expected


def test_multiblock_per_block_dictionaries_differ():
    """The same tag value gets DIFFERENT ids in different blocks — the
    per-page term tables must still resolve correctly."""
    from tempo_tpu.search.multiblock import (
        MultiBlockEngine, compile_multi, stack_blocks,
    )

    a = SearchData(trace_id=b"\x01" * 16, start_s=10, end_s=20, dur_ms=5)
    a.kvs = {"k": {"target"}, "zz": {"aaaa"}}
    b = SearchData(trace_id=b"\x02" * 16, start_s=10, end_s=20, dur_ms=5)
    b.kvs = {"k": {"target"}, "aa": {"zzzz"}}  # shifts dictionary ids
    c = SearchData(trace_id=b"\x03" * 16, start_s=10, end_s=20, dur_ms=5)
    c.kvs = {"k": {"other"}}
    blocks = [ColumnarPages.build([a], PageGeometry(4, 8)),
              ColumnarPages.build([b, c], PageGeometry(4, 8))]
    req = _mk_req({"k": "target"})
    req.limit = 10
    mq = compile_multi(blocks, req)
    batch = stack_blocks(blocks)
    eng = MultiBlockEngine()
    count, _, scores, idx = eng.scan(batch, mq)
    assert count == 2
    got = {m.trace_id for m in eng.results(batch, mq, scores, idx)}
    assert got == {(b"\x01" * 16).hex(), (b"\x02" * 16).hex()}


def test_stack_host_narrows_kv_dtypes():
    """VERDICT r4 #2: small dictionaries stack as int8/int16 so HBM
    bytes and the evicted-group re-stage shrink; results stay identical
    to the int32 path (the kernel promotes inline)."""
    import numpy as np

    from tempo_tpu.search.multiblock import (
        MultiBlockEngine, compile_multi, stack_blocks, stack_host,
    )

    blocks = [ColumnarPages.build(_corpus(40, seed=s), PageGeometry(8, 8))
              for s in range(3)]
    host = stack_host(blocks)
    assert host.cat["kv_key"].dtype == np.int8
    assert host.cat["kv_val"].dtype in (np.int8, np.int16)
    # padded slots keep the -1 sentinel through the cast
    assert (host.cat["kv_key"] >= -1).all()

    # NB: not the ("service.name", "front") pair — the global compile
    # cache is keyed by (dict fingerprint, tag-sig) and
    # test_compile_cache_skips_dictionary_probe asserts that pair cold
    req = _mk_req({"service.name": "ront"})
    req.limit = 1000
    mq = compile_multi(blocks, req)
    eng = MultiBlockEngine()
    count, inspected, scores, idx = eng.scan(stack_blocks(blocks), mq)
    expected = sum(
        1 for s in range(3) for sd in _corpus(40, seed=s)
        if any("ront" in v for v in sd.kvs.get("service.name", ())))
    assert int(count) == expected


def test_stack_host_wide_dicts_stay_int32():
    import numpy as np

    from tempo_tpu.search.multiblock import stack_host

    b = ColumnarPages.build(_corpus(20), PageGeometry(8, 8))
    b.val_dict = b.val_dict + [f"v{i:07d}" for i in range(40_000)]
    host = stack_host([b])
    assert host.cat["kv_val"].dtype == np.int32


def test_compile_multi_skipped_group_wider_ranges():
    """code-review r5: a dict group whose EVERY row is header-skipped may
    compile more disjoint value-id ranges than the unskipped width —
    assembly must clamp both axes, and the skipped rows end masked."""
    from tempo_tpu.search.multiblock import compile_multi

    a = SearchData(trace_id=b"\x01" * 16, start_s=10, end_s=20, dur_ms=5)
    a.kvs = {"k": {"svcA"}}
    # disjoint dictionary ids for the substring "svc" → R_cq = 2 ranges
    b = SearchData(trace_id=b"\x02" * 16, start_s=10, end_s=20, dur_ms=5)
    b.kvs = {"k": {"asvcq"}, "m": {"bbb"}, "n": {"csvcq"}}
    blocks = [ColumnarPages.build([a], PageGeometry(4, 8)),
              ColumnarPages.build([b], PageGeometry(4, 8))]
    req = _mk_req({"k": "svc"})
    req.limit = 10
    mq = compile_multi(blocks, req, skip=[False, True])
    assert mq is not None
    assert (mq.term_keys[1] == -1).all()          # skipped row masked
    assert (mq.val_ranges[1, :, :, 0] == 1).all()  # empty [1,0] ranges
    assert (mq.val_ranges[1, :, :, 1] == 0).all()
    assert (mq.term_keys[0] != -1).any()           # live row intact


def test_compile_cache_skips_dictionary_probe():
    """Per-(block, tag-set) compile cache (VERDICT r2 #1): the second
    compilation of the same tags against the same block skips the
    dictionary probe entirely; different scalars (window/duration/limit)
    reuse the cached probe; different tags or the prune result are
    cached separately."""
    from unittest import mock

    from tempo_tpu.search import pipeline
    from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
    pages = ColumnarPages.build(_corpus(50), PageGeometry(16, 8))
    # a needle of this test's own: the compile cache is process-wide,
    # keyed by dictionary content, and other tests share this corpus
    req = _mk_req({"service.name": "fronten"})
    req.limit = 5

    with mock.patch.object(pipeline, "substring_value_ids",
                           wraps=pipeline.substring_value_ids) as probe:
        cq1 = pipeline.compile_query(pages.key_dict, pages.val_dict, req,
                                     cache_on=pages)
        n_cold = probe.call_count
        assert n_cold >= 1
        # same tags, different scalars -> cache hit, fresh scalars
        req2 = _mk_req({"service.name": "fronten"})
        req2.limit = 99
        req2.min_duration_ms = 123
        cq2 = pipeline.compile_query(pages.key_dict, pages.val_dict, req2,
                                     cache_on=pages)
        assert probe.call_count == n_cold  # no new probes
        assert cq2.limit == 99 and cq2.dur_lo == 123
        assert (cq1.term_keys == cq2.term_keys).all()
        assert (cq1.val_ranges == cq2.val_ranges).all()

        # pruned result cached too
        miss = _mk_req({"no.such.key": "x"})
        assert pipeline.compile_query(pages.key_dict, pages.val_dict, miss,
                                      cache_on=pages) is None
        n_after_miss = probe.call_count
        assert pipeline.compile_query(pages.key_dict, pages.val_dict, miss,
                                      cache_on=pages) is None
        assert probe.call_count == n_after_miss

    # uncached path still works (no cache_on)
    cq3 = pipeline.compile_query(pages.key_dict, pages.val_dict, req)
    assert (cq3.term_keys == cq1.term_keys).all()


def test_engine_randomized_differential_vs_oracle():
    """Property fuzz: random corpora × random predicates × random page
    geometry must agree EXACTLY with the host oracle — fixed query lists
    miss edge interactions (empty windows, dur bounds at the sample
    values, substring terms matching zero/all dictionary entries)."""
    rng = random.Random(1234)
    for round_ in range(25):
        entries = _corpus(n=rng.randint(1, 300), seed=rng.randint(0, 10**6))
        E = rng.choice([8, 64, 256])
        C = rng.choice([4, 8, 16])
        pages = ColumnarPages.build(entries, PageGeometry(E, C))

        tags = {}
        for _ in range(rng.randint(0, 3)):
            k = rng.choice(["service.name", "http.status_code", "region",
                            "component", "nope.key"])
            v = rng.choice(["front", "frontend", "cart", "5", "500", "us",
                            "db", "zz-none", ""])
            if v:
                tags[k] = v
        kw = {}
        if rng.random() < 0.5:
            kw["min_duration_ms"] = rng.choice([1, 500, 5_000, 30_000])
        if rng.random() < 0.5:
            kw["max_duration_ms"] = rng.choice([100, 5_000, 60_000])
        if rng.random() < 0.5:
            kw["start"] = 1_600_000_000 + rng.randint(-50, 400)
            kw["end"] = kw["start"] + rng.randint(0, 300)
        req = _mk_req(tags, **kw)
        req.limit = 1000

        expected = {sd.trace_id for sd in entries
                    if search_data_matches(sd, req)}
        got = scan_batch([pages], req, top_k=1024)
        if got.mq is None:
            assert not expected, (round_, tags, kw)
            continue
        assert got.count == len(expected), (round_, tags, kw)
        assert got.inspected == len(entries)
        assert got.trace_ids == expected, (round_, tags, kw)
