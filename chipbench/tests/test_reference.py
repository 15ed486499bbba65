"""The reference agrees with the semantics of chip_smoke.py's
`Query.scan_block` (the reference PR 21 proved on the chip) on a seeded
tiny corpus, a limit-filling query included."""

import os
import sys
import tempfile
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import reference  # noqa: E402
from chipbench.generators import otel_blocks as gen  # noqa: E402

PARAMS = dict(
    config_name="test", tenant="t", blocks=5, entries_per_block=3000,
    services=200, routes=500, rpc_methods=300, pods=2000, customers=10000,
    span_names=400, zipf_s=1.1, dur_median_ms=40, dur_sigma=1.787,
    time_base=1_700_000_000, time_span_s=86400, time_overlap=0.1)

QUERIES = [
    dict(tags={"service.name": "ads-api"}, limit=20),            # fills
    dict(tags={"service.name": "ads-api"}, limit=20, exhaustive=True),
    dict(tags={"service.name": "payments-ledger",
               "http.status_code": "500"}, min_ms=200, limit=20),
    dict(tags={"service.name": "-gateway", "cloud.region": "eu-west-1"},
         limit=20),
    dict(tags={}, min_ms=10_000, limit=20),
    dict(tags={"service.name": "ads-api"}, start=1_700_000_000 + 20_000,
         end=1_700_000_000 + 23_600, limit=20),
    dict(tags={"service.name": "no-such-service"}, limit=20),
    dict(tags={"no.such.key": "x"}, limit=20),
    dict(tags={"customer.id": "cus_"}, max_ms=3, limit=1000),
]


@pytest.fixture(scope="module")
def corpus():
    with tempfile.TemporaryDirectory() as d, ThreadPoolExecutor(2) as pool:
        m = gen.generate(PARAMS, 2**31 + 12345, d, pool)
        from tempo_tpu.backend.local import LocalBackend
        from tempo_tpu.backend.types import NAME_SEARCH
        from tempo_tpu.encoding.v2.compression import decompress
        from tempo_tpu.search.columnar import ColumnarPages

        be = LocalBackend(d)
        m["pages_read_back"] = [
            ColumnarPages.from_bytes(decompress(
                be.read("t", bid, NAME_SEARCH), "zstd"))
            for bid in m["block_ids"]]
    return m


@pytest.mark.parametrize("q", QUERIES, ids=lambda q: repr(q)[:60])
def test_reference_equals_smoke_scan(corpus, q):
    from chip_smoke import Query, entry_key

    sq = Query("q", q.get("tags"), min_ms=q.get("min_ms", 0),
               max_ms=q.get("max_ms", 0), start=q.get("start", 0),
               end=q.get("end", 0), limit=q["limit"],
               exhaustive=q.get("exhaustive", False))
    for b, pages in enumerate(corpus["pages_read_back"]):
        h = pages.header
        # the smoke's blocks had no header rollups; the program skips a
        # block whose rollup excludes the window or the duration bound
        # (search/pipeline.block_header_skip_reason)
        if not sq.exhaustive and (
                (sq.start and h["max_end_s"] < sq.start)
                or (sq.end and h["min_start_s"] > sq.end)
                or (sq.min_ms and h["max_dur_ms"] < sq.min_ms)
                or (sq.max_ms and h["min_dur_ms"] > sq.max_ms)):
            sq.skipped_blocks += 1
            continue
        sq.scan_block(b, pages)
    sq.seal()
    got = reference.answer(q, corpus)
    assert got["inspected"] == sq.inspected
    assert got["skipped_blocks"] == sq.skipped_blocks
    assert got["matches"] == sq.matches
    assert got["deterministic"] == sq.deterministic
    assert got["keys"].tolist() == sq.keys.tolist()
    assert got["top_starts"] == np.sort(sq.starts)[::-1][:q["limit"]].tolist()
    for key in sq.keys[:50].tolist():
        assert reference.entry_matches(q, corpus, key >> 32,
                                       key & 0xFFFFFFFF)
    assert entry_key(1, 2) == (1 << 32) | 2


def test_limit_filling_query_is_not_deterministic(corpus):
    got = reference.answer(QUERIES[0], corpus)
    assert got["matches"] >= 20 and not got["deterministic"]
    miss = np.flatnonzero(
        corpus["vals"][0, list(corpus["key_names"]).index("service.name")]
        != list(corpus["table"]).index("ads-api"))[0]
    assert not reference.entry_matches(QUERIES[0], corpus, 0, int(miss))


def test_block_ids_do_not_depend_on_the_seed_and_fill_groups():
    from tempo_tpu.search.batcher import BlockBatcher, ScanJob

    ids = [gen.block_id("some-config", i, 64) for i in range(200)]
    assert ids == [gen.block_id("some-config", i, 64) for i in range(200)]
    assert len(set(ids)) == 200
    jobs = [ScanJob(key=(b, 0, 64), pages_fn=None, header={}, n_pages=64,
                    n_entries=65536, geometry=(1024, 16)) for b in ids]
    bb = BlockBatcher.__new__(BlockBatcher)
    bb.max_batch_pages = 4096
    groups = bb.plan(jobs)
    assert sorted(len(g) for g in groups) == [8, 64, 64, 64]
