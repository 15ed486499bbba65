"""Packed HBM residency (tempo_tpu/search/packing.py).

The tentpole contract (docs/search-packed-residency.md): staged
value-id columns narrow to the width the per-block dictionary
cardinality allows (4-bit/uint8/uint16/uint32 codes), durations
quantize to uint16 buckets with an exact residual check at bucket
boundaries, device-probe hit masks bit-pack to uint32 words — and the
kernels unpack in-register behind a static width descriptor, so

  - `search_packed_residency: true` is byte-identical to false across
    every engine path (single, batched, coalesced, mesh-sharded,
    distributed) and the dict-probe mask-lookup path;
  - the disabled path is a true noop: legacy layout, widths None,
    logical == physical accounting;
  - physical staged bytes strictly shrink on width-winning corpora,
    and the logical/physical split is visible in the batcher totals
    and the per-query stats.

Cardinalities deliberately straddle every width boundary (15/16/17,
255/256/257, 65535/65536/65537) and durations sit on quantization
bucket edges — the places an off-by-one in the code shift or the
boundary-residual logic would first go wrong.
"""

import numpy as np
import pytest

from tempo_tpu import tempopb
from tempo_tpu.search import packing, pipeline
from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
from tempo_tpu.search.data import SearchData
from tempo_tpu.search.multiblock import (
    MultiBlockEngine,
    compile_multi,
    stack_host,
    stack_queries,
)


@pytest.fixture(autouse=True)
def _packing_off_and_cold_cache():
    """Each test flips the process-wide gate itself; leave the process
    exactly as found (gate off) and keep compile-cache products from
    one gate state out of the next test's assertions."""
    packing.configure(enabled=False)
    pipeline._COMPILE_CACHE.clear()
    yield
    packing.configure(enabled=False)
    pipeline._COMPILE_CACHE.clear()


def _corpus(n, n_vals, seed, dur_max=50_000, E=64, extra_durs=(),
            n_tags=2):
    rng = np.random.default_rng(seed)
    durs = list(rng.integers(0, dur_max, size=n).tolist())
    for i, d in enumerate(extra_durs):
        durs[i % n] = int(d)
    entries = []
    for i in range(n):
        sd = SearchData(
            trace_id=rng.bytes(16),
            start_s=int(rng.integers(1, 2_000)),
            end_s=int(rng.integers(2_000, 4_000)),
            dur_ms=durs[i],
        )
        sd.kvs = {
            "service.name": {f"svc-{int(rng.integers(0, n_vals)):07d}"},
            "http.path": {f"/p/{int(rng.integers(0, n_vals)):07d}"},
        }
        for t in range(2, n_tags):
            sd.kvs[f"tag{t}"] = {
                f"t{t}-{int(rng.integers(0, n_vals)):07d}"}
        entries.append(sd)
    return ColumnarPages.build(entries, PageGeometry(E, 64))


def _req(tags=None, **kw):
    req = tempopb.SearchRequest()
    for k, v in (tags or {}).items():
        req.tags[k] = v
    for k, v in kw.items():
        setattr(req, k, v)
    return req


def _canon(out):
    count, inspected, scores, idx = out
    return (int(count), int(inspected),
            np.asarray(scores).tolist(), np.asarray(idx).tolist())


# ---------------------------------------------------------------------------
# width selection + host-side pack/unpack units


def test_width_boundaries_straddle_exactly():
    # n values need n+1 codes (pad reserves 0), so 16/256/65536 tip over
    assert [packing.width_for_cardinality(n) for n in (15, 16, 17)] \
        == ["u4", "u8", "u8"]
    assert [packing.width_for_cardinality(n) for n in (255, 256, 257)] \
        == ["u8", "u16", "u16"]
    assert [packing.width_for_cardinality(n)
            for n in (65535, 65536, 65537)] == ["u16", "u32", "u32"]


def test_dur_width_rule():
    assert packing.dur_width(0xFFFF) == "u16"
    assert packing.dur_width(0x10000) == "q1"
    assert packing.dur_width((1 << 24) - 1) == "q8"   # residual uint8
    assert packing.dur_width(0xFFFFFFFF) == "q16"


def test_pack_unpack_ids_roundtrip_all_widths():
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    for w, n in (("u4", 15), ("u8", 255), ("u16", 65_535), ("u32", 70_000)):
        ids = rng.integers(-1, n, size=(3, 5, 8), dtype=np.int64) \
            .astype(np.int32)
        packed = packing.pack_ids_array(ids, w)
        back = np.asarray(packing.unpack_ids(jnp.asarray(packed), w))
        assert np.array_equal(back, ids), w
        # the packed format really is narrower where it should be
        if w == "u4":
            assert packed.nbytes == ids.nbytes // 8


def test_duration_ok_exact_on_bucket_edges():
    """Property: quantized-bucket + boundary-residual compare ==
    exact uint32 range compare, including bounds and durations sitting
    exactly ON bucket edges."""
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    for s in (1, 5, 8, 11, 16):
        dw = f"q{s}"
        top = min(1 << 32, 1 << (16 + s))
        dur = rng.integers(0, top, size=256, dtype=np.int64)
        edges = []
        for m in (0, 1, 2, 7, 100):
            for d in (-1, 0, 1):
                edges.append((m << s) + d)
        dur = np.concatenate([
            dur, np.clip(np.array(edges, dtype=np.int64), 0, top - 1)
        ]).astype(np.uint32)
        q, r = packing.pack_duration(dur, dw)
        assert q.dtype == np.uint16
        assert r.dtype == (np.uint8 if s <= 8 else np.uint16)
        bounds = [(0, 0xFFFFFFFF), (1 << s, (3 << s) - 1),
                  ((1 << s) + 1, 3 << s), (5, 5), ((2 << s) - 1, 2 << s)]
        for _ in range(4):
            lo, hi = sorted(rng.integers(0, top, size=2).tolist())
            bounds.append((lo, hi))
        for lo, hi in bounds:
            got = np.asarray(packing.duration_ok(
                jnp.asarray(q), jnp.asarray(r),
                jnp.uint32(lo), jnp.uint32(hi), dw))
            want = (dur >= np.uint32(lo)) & (dur <= np.uint32(hi))
            assert np.array_equal(got, want), (s, lo, hi)


def test_mask_words_roundtrip():
    import jax.numpy as jnp

    from tempo_tpu.search import dict_probe

    rng = np.random.default_rng(5)
    hits = rng.random((3, 130)) < 0.3
    words = np.asarray(packing.pack_mask_words(jnp.asarray(hits)))
    assert words.dtype == np.uint32 and words.shape == (3, 5)
    back = packing.unpack_mask_words(words, 130)
    assert np.array_equal(back, hits)
    # hits_to_ids accepts both formats
    for t in range(3):
        assert dict_probe.hits_to_ids(words[t]).tolist() \
            == np.nonzero(hits[t])[0].tolist()


# ---------------------------------------------------------------------------
# noop contract: gate off = the legacy layout exactly


def test_disabled_gate_keeps_legacy_layout():
    blocks = [_corpus(100, 200, 1), _corpus(100, 14, 2)]
    host = stack_host(blocks, pad_to=8)
    assert host.widths is None
    assert "entry_dur_res" not in host.cat
    assert host.cat["kv_key"].dtype == np.int8
    assert host.cat["kv_val"].dtype == np.int16   # 200 vals > 127
    assert host.cat["entry_dur"].dtype == np.uint32
    # logical == physical when nothing is packed
    assert host.cat_logical_nbytes == host.cat_nbytes


def test_single_block_fast_path_serves_views():
    """One block already matching the bucket shape skips the
    concatenate+pad copy: the fixed-width columns are served as views
    of the block's own arrays."""
    b = _corpus(64, 300, 3)  # 1 page of 64 entries: bucket-exact
    host = stack_host([b], pad_to=b.n_pages)
    assert np.shares_memory(host.cat["entry_start"], b.entry_start)
    assert np.shares_memory(host.cat["entry_valid"], b.entry_valid)
    # a padded stack still copies (and must)
    host2 = stack_host([b], pad_to=b.n_pages + 1)
    assert not np.shares_memory(host2.cat["entry_start"], b.entry_start)


def test_packed_strictly_fewer_physical_bytes_logical_split():
    # tag-heavy corpus (the realistic shape — kv is ~70% of a batch's
    # bytes): 14 tag keys (u4), ≤ 210 distinct values (u8 vs the
    # legacy int16 narrowing), durations within uint16
    blocks = [_corpus(200, 7, 4, n_tags=14), _corpus(200, 15, 5, n_tags=14)]
    assert max(len(b.key_dict) for b in blocks) <= 15
    assert max(len(b.val_dict) for b in blocks) <= 255
    off = stack_host(blocks, pad_to=16)
    packing.configure(enabled=True)
    on = stack_host(blocks, pad_to=16)
    assert on.widths == ("u4", "u8", "u16")
    assert on.cat_nbytes < off.cat_nbytes
    # logical view reports the unpacked layout on both sides
    assert on.cat_logical_nbytes == off.cat_nbytes == off.cat_logical_nbytes
    # > 40% narrower on this corpus shape (the bench target)
    assert on.cat_nbytes < 0.6 * off.cat_nbytes, \
        (on.cat_nbytes, off.cat_nbytes)


# ---------------------------------------------------------------------------
# differential parity: packed on ≡ off, per engine path

# duration bounds sitting exactly on q-bucket edges for the >65535
# corpora (s = 5 at max_dur ~2^21)
_EDGE = 1 << 5


def _parity_blocks():
    return [
        _corpus(120, 15, 21),                       # u4 boundary low
        _corpus(120, 16, 22),
        _corpus(120, 255, 23),
        _corpus(120, 257, 24),
        _corpus(120, 300, 25, dur_max=1 << 21,      # forces q-width
                extra_durs=(3 * _EDGE - 1, 3 * _EDGE, 3 * _EDGE + 1,
                            7 * _EDGE, 0)),
    ]


def _parity_reqs():
    return [
        _req({"service.name": "svc-0000003"}, limit=20),
        _req({"http.path": "/p/000000"}, limit=500),
        _req(min_duration_ms=3 * _EDGE, max_duration_ms=7 * _EDGE,
             limit=100),
        _req(min_duration_ms=3 * _EDGE + 1, max_duration_ms=7 * _EDGE - 1,
             limit=100),
        _req({"service.name": "svc"}, min_duration_ms=1, limit=1000),
    ]


def _run_multi(eng, blocks, req):
    host = eng.stage_host(blocks)
    batch = eng.place(host)
    mq = compile_multi(blocks, req, cache_on=batch)
    if mq is None:
        return ("pruned",)
    return _canon(eng.scan(batch, mq))


def test_parity_batched_engine():
    eng = MultiBlockEngine(top_k=64)
    blocks = _parity_blocks()
    for req in _parity_reqs():
        off = _run_multi(eng, blocks, req)
        pipeline._COMPILE_CACHE.clear()
        packing.configure(enabled=True)
        on = _run_multi(eng, blocks, req)
        packing.configure(enabled=False)
        pipeline._COMPILE_CACHE.clear()
        assert on == off, req


def test_parity_one_block_batches():
    """Each block alone, a one-block batch: the widths a single block's
    own dictionaries choose (a batch of five shares the widest)."""
    eng = MultiBlockEngine(top_k=64)
    for b in _parity_blocks():
        for req in _parity_reqs():
            off = _run_multi(eng, [b], req)
            pipeline._COMPILE_CACHE.clear()
            packing.configure(enabled=True)
            assert eng.stage_host([b]).widths is not None
            on = _run_multi(eng, [b], req)
            packing.configure(enabled=False)
            pipeline._COMPILE_CACHE.clear()
            assert on == off, req


def test_parity_coalesced_engine():
    from tempo_tpu.search.engine import fetch_scan_out

    eng = MultiBlockEngine(top_k=32)
    blocks = _parity_blocks()
    reqs = _parity_reqs()[:3]

    def run():
        host = eng.stage_host(blocks)
        batch = eng.place(host)
        mqs = [compile_multi(blocks, r, cache_on=batch) for r in reqs]
        cq = stack_queries(mqs)
        out = fetch_scan_out(
            eng.coalesced_scan_async(batch, cq, top_k=32))
        return (out[0].tolist(), int(out[1]),
                out[2].tolist(), out[3].tolist())

    off = run()
    pipeline._COMPILE_CACHE.clear()
    packing.configure(enabled=True)
    on = run()
    assert on == off


def test_parity_mesh_engine():
    from tempo_tpu.parallel.mesh import make_mesh

    eng = MultiBlockEngine(top_k=32, mesh=make_mesh())
    blocks = _parity_blocks()
    for req in _parity_reqs()[:3]:
        off = _run_multi(eng, blocks, req)
        pipeline._COMPILE_CACHE.clear()
        packing.configure(enabled=True)
        on = _run_multi(eng, blocks, req)
        packing.configure(enabled=False)
        pipeline._COMPILE_CACHE.clear()
        assert on == off, req


def test_parity_mesh_one_block_batch():
    """One block's pages sharded over the mesh (the q-width corpus)."""
    from tempo_tpu.parallel.mesh import make_mesh

    eng = MultiBlockEngine(top_k=32, mesh=make_mesh())
    b = _parity_blocks()[4]
    for req in _parity_reqs():
        off = _run_multi(eng, [b], req)
        pipeline._COMPILE_CACHE.clear()
        packing.configure(enabled=True)
        assert eng.stage_host([b]).widths is not None
        on = _run_multi(eng, [b], req)
        packing.configure(enabled=False)
        pipeline._COMPILE_CACHE.clear()
        assert on == off, req


@pytest.fixture
def mask_products(monkeypatch):
    """Every device probe product as a hit mask (R_MAX 0): the shipped
    rule turns these corpora's one-run needles into ranges, and the
    packed-mask format would go untested."""
    from tempo_tpu.search import dict_probe

    monkeypatch.setattr(dict_probe, "R_MAX", 0)


def test_parity_dict_probe_mask_path(mask_products):
    """The mask-lookup membership path with bit-packed hit masks must
    agree with the unpacked masks AND the pure host range path, over a
    batch mixing device-probed and host-compiled blocks."""
    from tempo_tpu.search.multiblock import stack_blocks

    rng = np.random.default_rng(31)
    big = _corpus(150, 120, 41)      # 120 distinct values >= threshold 50
    small = _corpus(150, 10, 42)     # below threshold: host range path
    blocks = [big, small]
    reqs = [_req({"service.name": "svc-00000"}, limit=200),
            _req({"service.name": f"svc-{int(rng.integers(0, 120)):07d}"},
                 limit=50),
            _req({"http.path": "/p/"}, min_duration_ms=100, limit=300)]

    def run(probe_min_vals):
        batch = stack_blocks(blocks, pad_to=16,
                             probe_min_vals=probe_min_vals)
        eng = MultiBlockEngine(top_k=64)
        outs = []
        for req in reqs:
            mq = compile_multi(blocks, req, cache_on=batch)
            if probe_min_vals:
                assert mq.val_hits is not None  # the probe path really ran
                if packing.PACKING.enabled:
                    assert packing.is_packed_mask(mq.val_hits)
            outs.append(_canon(eng.scan(batch, mq)))
            pipeline._COMPILE_CACHE.clear()
        return outs

    host_only = run(0)
    probed_off = run(50)
    packing.configure(enabled=True)
    probed_on = run(50)
    assert probed_off == host_only
    assert probed_on == host_only


def test_host_scan_parity_over_packed_host_batch():
    """The breaker/ownership host route runs the same kernel over the
    host-tier arrays — which stage the SAME packed layout — and must
    stay byte-identical to the packed device dispatch."""
    from tempo_tpu.search.batcher import host_scan

    from tempo_tpu.search.engine import resolve_top_k

    eng = MultiBlockEngine(top_k=64)
    blocks = _parity_blocks()
    packing.configure(enabled=True)
    host = eng.stage_host(blocks)
    batch = eng.place(host)
    for req in _parity_reqs()[:3]:
        mq = compile_multi(blocks, req, cache_on=batch)
        dev = _canon(eng.scan(batch, mq))
        hb = host_scan(host, mq, resolve_top_k(eng.top_k, mq.limit))
        assert _canon(hb) == dev, req


def test_compile_cache_mask_format_flip_is_a_miss(mask_products):
    """A cached probe product minted under the other gate state must
    recompile, not leak the wrong mask format into an assembled batch."""
    from tempo_tpu.search.multiblock import stack_blocks

    b = _corpus(150, 120, 43)
    req = _req({"service.name": "svc-00000"}, limit=100)
    batch = stack_blocks([b], pad_to=8, probe_min_vals=50)
    mq_off = compile_multi([b], req, cache_on=batch)
    assert not packing.is_packed_mask(mq_off.val_hits)
    packing.configure(enabled=True)
    batch2 = stack_blocks([b], pad_to=8, probe_min_vals=50)
    mq_on = compile_multi([b], req, cache_on=batch2)
    assert packing.is_packed_mask(mq_on.val_hits)


# ---------------------------------------------------------------------------
# serving path end to end: TempoDB responses + accounting split


def _write_blocks(be, n_blocks):
    import json

    from tempo_tpu.backend.types import (
        BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER,
    )
    from tempo_tpu.encoding.v2.compression import compress

    metas = []
    for s in range(n_blocks):
        pages = _corpus(256, [14, 200, 300][s % 3], 100 + s, E=64)
        m = BlockMeta(tenant_id="t", encoding="none")
        blob = compress(pages.to_bytes(), "none")
        hdr = dict(pages.header)
        hdr["encoding"] = "none"
        hdr["compressed_size"] = len(blob)
        be.write("t", m.block_id, NAME_SEARCH, blob)
        be.write("t", m.block_id, NAME_SEARCH_HEADER,
                 json.dumps(hdr).encode())
        metas.append(m)
    return metas


def test_tempodb_serving_byte_identical_and_accounted(tmp_path):
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig

    be = LocalBackend(str(tmp_path / "blocks"))
    metas = _write_blocks(be, 6)
    req = _req({"service.name": "svc-0000001"}, limit=10_000)

    def serve(tag, enabled):
        db = TempoDB(be, str(tmp_path / f"wal-{tag}"), TempoDBConfig(
            auto_mesh=False, search_max_batch_pages=8,
            search_coalesce_max_queries=0, host_state_dir="",
            search_packed_residency=enabled))
        db.blocklist.update("t", add=metas)
        resp = db.search("t", req).response()
        resp.metrics.device_seconds = 0.0
        phys = db.batcher.cache.snapshot()["hbm_bytes"]
        logical = db.batcher.debug_stats()["hbm_cache"]["logical_bytes"]
        return resp.SerializeToString(), phys, logical

    off, phys_off, logical_off = serve("off", False)
    on, phys_on, logical_on = serve("on", True)
    assert on == off
    assert phys_on < phys_off
    # logical totals are layout-independent; physical sits strictly
    # below them when packed (the budget totals also carry the uploaded
    # per-predicate query tables, which the logical split leaves out)
    assert logical_on == logical_off
    assert phys_on < logical_on
    # gauges publish the split
    from tempo_tpu.observability import metrics as obs

    assert obs.hbm_logical_bytes.value() == logical_on
    packing.configure(enabled=False)


def test_query_stats_staged_bytes_split(tmp_path):
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.search import query_stats

    be = LocalBackend(str(tmp_path / "blocks"))
    metas = _write_blocks(be, 3)
    db = TempoDB(be, str(tmp_path / "wal"), TempoDBConfig(
        auto_mesh=False, search_max_batch_pages=8, host_state_dir="",
        search_coalesce_max_queries=0, search_packed_residency=True))
    db.blocklist.update("t", add=metas)
    # TempoDB.search opens its own exec-scope record; read it back from
    # the registry ring like /debug/querystats does
    query_stats.configure(enabled=True)
    db.search("t", _req({"service.name": "svc"}, limit=10_000))
    d = list(query_stats.REGISTRY._ring)[-1]
    sb = d.get("staged_bytes")
    assert sb and 0 < sb["physical"] < sb["logical"]
    packing.configure(enabled=False)


# ---------------------------------------------------------------------------
# persistent compile cache hits surface in the jit-cache counter
# (where the cache lives: tests/test_compile_cache.py)


def test_persisted_compile_cache_hits_are_counted():
    from jax import monitoring

    from tempo_tpu.observability import metrics as obs
    from tempo_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()  # registers the listener (idempotent)
    before = obs.jit_cache_events.value(result="persisted")
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    assert obs.jit_cache_events.value(result="persisted") == before + 1
