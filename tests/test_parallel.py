import jax
import numpy as np
import pytest

from tempo_tpu import tempopb
from tempo_tpu.parallel import make_mesh
from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
from tempo_tpu.search.data import search_data_matches

from conftest import scan_batch
from tests.test_search import _corpus, _mk_req, QUERIES


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8
    mesh = make_mesh()
    assert mesh.devices.size == 8


@pytest.mark.parametrize("qi", [0, 2, 4, 7])
def test_one_block_batch_mesh_matches_no_mesh(qi):
    """One block is a one-block batch: its pages sharded over the mesh
    answer what the same batch answers on one device."""
    req = QUERIES[qi]
    req.limit = 1000
    entries = _corpus(500)
    pages = ColumnarPages.build(entries, PageGeometry(32, 8))
    single = scan_batch([pages], req, top_k=1024)
    if single.mq is None:
        pytest.skip("query prunes block")
    dist = scan_batch([pages], req, top_k=1024, mesh=make_mesh())

    assert dist.count == single.count
    assert dist.inspected == single.inspected
    assert dist.canon() == single.canon()

    expected = {sd.trace_id for sd in entries if search_data_matches(sd, req)}
    assert dist.trace_ids == expected


def test_one_block_batch_shards_pages():
    from tempo_tpu.search.multiblock import MultiBlockEngine

    entries = _corpus(300)
    pages = ColumnarPages.build(entries, PageGeometry(32, 8))
    batch = MultiBlockEngine(mesh=make_mesh()).stage([pages])
    arr = batch.device["kv_key"]
    assert arr.shape[0] % 8 == 0
    # each of the 8 devices holds a distinct contiguous page shard
    assert len(arr.sharding.device_set) == 8


# ---------------------------------------------------------------------------
# round 2: the distributed MULTI-BLOCK kernel (the serving path on a mesh)


def _blocks(n_blocks, per_block, geometry=PageGeometry(32, 8)):
    all_entries, blocks = [], []
    for b in range(n_blocks):
        entries = _corpus(per_block, seed=b * 7 + 1)
        all_entries.append(entries)
        blocks.append(ColumnarPages.build(entries, geometry))
    return all_entries, blocks


@pytest.mark.parametrize("qi", [0, 2, 4, 7])
def test_dist_multiblock_matches_single_device(qi):
    """Mesh-sharded batched scan == single-device batched scan == host
    oracle, including result identity (not just counts)."""
    from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi

    req = QUERIES[qi]
    req.limit = 2000
    all_entries, blocks = _blocks(5, 120)
    mq = compile_multi(blocks, req)
    if mq is None:
        pytest.skip("query prunes every block")

    single = MultiBlockEngine(top_k=1024)
    sb = single.stage(blocks)
    s_count, s_inspected, s_scores, s_idx = single.scan(sb, mq)

    dist = MultiBlockEngine(top_k=1024, mesh=make_mesh())
    db_ = dist.stage(blocks)
    d_count, d_inspected, d_scores, d_idx = dist.scan(db_, mq)

    assert d_count == s_count and d_inspected == s_inspected

    expected = {sd.trace_id for entries in all_entries for sd in entries
                if search_data_matches(sd, req)}
    got_single = {bytes.fromhex(m.trace_id)
                  for m in single.results(sb, mq, s_scores, s_idx)}
    got_dist = {bytes.fromhex(m.trace_id)
                for m in dist.results(db_, mq, d_scores, d_idx)}
    assert got_single == expected
    assert got_dist == expected


def test_dist_multiblock_uneven_pages_and_padding():
    """Blocks with uneven page counts (total not divisible by the shard
    count) pad with invalid pages; counts must ignore the padding."""
    from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi

    # 3 blocks x different sizes -> 3+1+2=6 pages, padded to 8 over mesh
    geometry = PageGeometry(32, 8)
    e1 = _corpus(90, seed=1)   # 3 pages
    e2 = _corpus(20, seed=2)   # 1 page
    e3 = _corpus(64, seed=3)   # 2 pages
    blocks = [ColumnarPages.build(e, geometry) for e in (e1, e2, e3)]
    req = _mk_req({})
    req.limit = 500
    mq = compile_multi(blocks, req)
    dist = MultiBlockEngine(top_k=512, mesh=make_mesh())
    batch = dist.stage(blocks)
    assert batch.device["kv_key"].shape[0] % 8 == 0
    count, inspected, scores, idx = dist.scan(batch, mq)
    assert inspected == 90 + 20 + 64
    assert count == sum(
        1 for e in (e1, e2, e3) for sd in e if search_data_matches(sd, req))


def test_dist_multiblock_pruned_block_in_batch():
    """A block whose dictionary prunes the query stays in the batch but
    contributes no matches on any shard."""
    from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi

    geometry = PageGeometry(32, 8)
    hit = _corpus(64, seed=1)
    miss = []
    for sd in _corpus(64, seed=2):
        sd.kvs = {"other.key": {"zzz"}}
        miss.append(sd)
    blocks = [ColumnarPages.build(hit, geometry),
              ColumnarPages.build(miss, geometry)]
    req = _mk_req({"service.name": "frontend"})
    req.limit = 500
    mq = compile_multi(blocks, req)
    assert mq is not None
    assert int(mq.term_keys[1, 0]) == -1  # second block pruned
    dist = MultiBlockEngine(top_k=512, mesh=make_mesh())
    count, _, scores, idx = dist.scan(dist.stage(blocks), mq)
    expected = {sd.trace_id for sd in hit
                if search_data_matches(sd, req)}
    assert count == len(expected)


def test_dist_multiblock_limit_exceeds_topk():
    """limit > engine top_k: top_k doubles until it covers the limit on
    the mesh path too (scores come back globally merged)."""
    from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi

    _, blocks = _blocks(4, 100)
    req = _mk_req({})
    req.limit = 300  # > top_k=64
    mq = compile_multi(blocks, req)
    dist = MultiBlockEngine(top_k=64, mesh=make_mesh())
    count, _, scores, idx = dist.scan(dist.stage(blocks), mq)
    assert count == 400
    assert scores.shape[0] >= 300  # top_k grew to cover the limit
    # indices must be unique, valid, and in score order
    assert len(set(idx.tolist())) == idx.shape[0]
    assert all(scores[i] >= scores[i + 1] for i in range(len(scores) - 1))


def test_tempodb_search_on_mesh_equals_no_mesh(tmp_path):
    """The SERVING entry on a mesh: TempoDB.search with auto-meshed
    devices returns byte-identical results to the single-device path."""
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig

    per_block = [_corpus(50, seed=b) for b in range(4)]

    def build(path, mesh):
        be = LocalBackend(str(path / "blocks"))
        db = TempoDB(be, str(path / "wal"),
                     TempoDBConfig(auto_mesh=False), mesh=mesh)
        for entries in per_block:
            db.write_block_direct(
                "t1",
                sorted((sd.trace_id, b"\x01", sd.start_s, sd.end_s)
                       for sd in entries),
                search_entries=entries)
        return db

    req = _mk_req({"service.name": "frontend"})
    req.limit = 500

    p1 = tmp_path / "nomesh"
    p1.mkdir()
    r1 = build(p1, None).search("t1", req).response()
    p2 = tmp_path / "mesh"
    p2.mkdir()
    db2 = build(p2, make_mesh())
    assert db2.batcher.engine.mesh is not None
    r2 = db2.search("t1", req).response()

    ids1 = sorted(t.trace_id for t in r1.traces)
    ids2 = sorted(t.trace_id for t in r2.traces)
    assert ids1 == ids2 and len(ids1) > 0
    assert r1.metrics.inspected_traces == r2.metrics.inspected_traces
