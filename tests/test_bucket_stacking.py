"""Shape-bucketed cross-plan stacking (ISSUE 16): heterogeneous
structural plans canonicalize into a small static family of bucket
shapes (structural.canonical_bucket) so MIXED-plan concurrent queries
fuse into one coalesced dispatch — byte-identical to solo execution and
to the host reference evaluator, because each member's exact plan rides
along as a per-query slot program whose pad slots are unreachable from
the result slot."""

from __future__ import annotations

import random
import threading

import pytest

from tempo_tpu.search import ir
from tempo_tpu.search import packing as packing_mod
from tempo_tpu.search.columnar import ColumnarPages
from tempo_tpu.search.data import SearchData
from tempo_tpu.search.multiblock import (
    MultiBlockEngine,
    compile_multi,
    stack_queries,
)
from tempo_tpu.search.structural import (
    STRUCTURAL,
    BucketedStructural,
    canonical_bucket,
    compile_structural,
)
from test_structural import (  # noqa: F401 — _structural_on is autouse
    E_GEO,
    _corpus,
    _expected_ids,
    _mk_req,
    _mkdb,
    _rand_trace,
    _reparam,
    _scan_ids,
    _structural_on,
)

# three DISTINCT plan shapes that land in ONE bucket: same flattened
# span tier (tag/dur/kind leaf + tag/dur leaf + child = NS 4), same
# trace tier (exists + root copy = NT 2), all relational
_MIXED_TRIPLE = (
    '{"exists": {"child": {"parent": {"tag": {"k": "service.name", '
    '"v": "api"}}, "child": {"dur": {"min_ms": 50}}}}}',
    '{"exists": {"child": {"parent": {"tag": {"k": "service.name", '
    '"v": "db"}}, "child": {"kind": "server"}}}}',
    '{"exists": {"child": {"parent": {"dur": {"min_ms": 10}}, '
    '"child": {"tag": {"k": "name", "v": "op"}}}}}',
)


# --------------------------------------------- canonicalization (unit)


def test_canonical_bucket_tiers_and_solo_fallback():
    exprs = [ir.parse(s) for s in _MIXED_TRIPLE]
    entries = _corpus(21, n=40)
    blocks = [ColumnarPages.build(entries, E_GEO)]
    plans = [compile_structural(e, blocks).plan for e in exprs]
    assert len(set(plans)) == 3, "triple must be plan-heterogeneous"
    buckets = {canonical_bucket(p, STRUCTURAL.bucket_max_nodes)
               for p in plans}
    assert len(buckets) == 1
    bk = buckets.pop()
    assert bk[0] == "bucket" and bk[3] is True
    # pow2 tiers: 3 span slots -> 4, exists + root copy -> 2
    assert bk[1] == 4 and bk[2] == 2
    # relation-free plans bucket SEPARATELY (has_rel in the descriptor)
    flat = compile_structural(
        ir.parse('{"and": [{"tag": {"k": "env", "v": "prod"}}, '
                 '{"dur": {"min_ms": 5}}]}'), blocks).plan
    fb = canonical_bucket(flat, STRUCTURAL.bucket_max_nodes)
    assert fb is not None and fb[3] is False and fb != bk
    # over the tier cap the plan "still goes solo": exact-plan grouping
    assert canonical_bucket(plans[0], 2) is None


def test_bucket_group_key_gate_and_fallback():
    entries = _corpus(22, n=40)
    blocks = [ColumnarPages.build(entries, E_GEO)]
    eng = MultiBlockEngine(top_k=128)
    batch = eng.stage(blocks)
    sts = []
    for src in _MIXED_TRIPLE:
        expr = ir.parse(src)
        sts.append(compile_structural(expr, blocks, cache_on=batch))
    STRUCTURAL.stack_enabled = True
    # gate OFF: one attribute read, exact-plan grouping kept — the
    # three plans get three distinct group keys
    assert STRUCTURAL.bucket_enabled is False
    assert STRUCTURAL.bucket_group_key(batch, sts[0]) is None
    keys_off = {STRUCTURAL.stack_group_key(batch, st) for st in sts}
    assert len(keys_off) == 3
    assert keys_off == {(id(batch), st.plan) for st in sts}
    # gate ON: all three share ONE (batch, bucket) key
    STRUCTURAL.bucket_enabled = True
    keys_on = {STRUCTURAL.stack_group_key(batch, st) for st in sts}
    assert len(keys_on) == 1
    (bid, bk) = keys_on.pop()
    assert bid == id(batch) and bk[0] == "bucket"
    # a plan past the tier cap falls back to its exact plan key
    STRUCTURAL.bucket_max_nodes = 2
    assert STRUCTURAL.stack_group_key(batch, sts[0]) \
        == (id(batch), sts[0].plan)


# ------------------------------------------------ fused differential


def _check_bucketed(entries, exprs, packed: bool, mesh=None):
    """Mixed-plan differential: the bucket-fused dispatch answers
    bit-for-bit identically to solo dispatches and the host reference
    evaluator, per member lane."""
    from tempo_tpu.search.engine import fetch_scan_out

    packing_mod.PACKING.enabled = packed
    half = len(entries) // 2
    b1 = ColumnarPages.build(entries[:half], E_GEO)
    b2 = ColumnarPages.build(entries[half:], E_GEO)
    spanless = [SearchData(trace_id=(20_000 + i).to_bytes(16, "big"),
                           start_s=1, end_s=2, dur_ms=100,
                           kvs={"env": {"prod"}}) for i in range(5)]
    blocks = [b1, b2, ColumnarPages.build(spanless, E_GEO)]
    eng = MultiBlockEngine(top_k=512, mesh=mesh)
    batch = eng.stage(blocks)
    mqs = []
    for expr in exprs:
        req = _mk_req(expr)
        mq = compile_multi(blocks, req, cache_on=batch)
        mq.structural = compile_structural(
            expr, blocks, cache_on=batch,
            staged_dicts=batch.staged_dicts)
        mq._expr = expr
        mqs.append(mq)
    # group exactly like bucket_group_key: same canonical bucket
    groups: dict = {}
    for mq in mqs:
        bk = canonical_bucket(mq.structural.plan,
                              STRUCTURAL.bucket_max_nodes)
        if bk is not None:
            groups.setdefault(bk, []).append(mq)
    checked = 0
    all_entries = entries + spanless
    E = E_GEO.entries_per_page
    for bk, group in groups.items():
        if len(group) < 2:
            continue
        if len({mq.structural.plan for mq in group}) < 2:
            continue  # same-plan groups take the exact-plan stack
        cq = stack_queries(group)
        assert isinstance(cq.structural, BucketedStructural)
        assert cq.structural.plan == bk
        assert cq.structural.active_nodes <= cq.structural.slot_nodes
        counts, _ins, scores, idx = fetch_scan_out(
            eng.coalesced_scan_async(batch, cq, 512))
        for qi, mq in enumerate(group):
            got = set()
            for s, i in zip(scores[qi].tolist(), idx[qi].tolist()):
                if s < 0:
                    break
                p, e = divmod(i, E)
                if p >= batch.n_pages:
                    continue
                bi = int(batch.page_block[p])
                if bi < 0:
                    continue
                lp = p - batch.page_offset[bi]
                got.add(bytes(batch.blocks[bi].trace_ids[lp, e]))
            want = _expected_ids(mq._expr, all_entries)
            scount, sgot = _scan_ids(batch, eng, mq, all_entries)
            assert got == want == sgot, (ir.to_json(mq._expr), packed)
            assert int(counts[qi]) == len(want) == scount
        checked += len(group)
    return checked


@pytest.mark.parametrize("packed", [False, True])
def test_bucketed_mixed_triple_matches_solo_and_host(packed):
    entries = _corpus(31, n=120)
    exprs = [ir.parse(s) for s in _MIXED_TRIPLE]
    assert _check_bucketed(entries, exprs, packed=packed) == 3


@pytest.mark.parametrize("packed", [False, True])
def test_bucketed_differential_fuzz_mixed_plans(packed):
    """The bucketing property: ANY random mixed-plan concurrent set
    whose members canonicalize into one bucket answers identically
    bucket-fused, solo, and on the reference evaluator — packed
    residency on and off."""
    rng = random.Random(80_000 + packed)
    checked = 0
    for round_i in range(6):
        entries = _corpus(900 + round_i, n=70)
        # random templates plus reparams: reparamming preserves tree
        # SHAPE but leaf dedup may shift exact plans apart — precisely
        # the mixed-plan-same-bucket traffic bucketing fuses
        exprs = []
        for _ in range(3):
            t = _rand_trace(rng)
            exprs += [t, _reparam(t, rng), _reparam(t, rng)]
        checked += _check_bucketed(entries, exprs, packed=packed)
    assert checked >= 4, "fuzz never produced a mixed-plan bucket group"


def test_bucketed_on_mesh_with_sharded_spans():
    """Bucketed stacking composes with the mesh path and segment-
    aligned span sharding, byte-identical throughout."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs multiple (forced host) devices")
    from tempo_tpu.parallel import make_mesh

    entries = _corpus(41, n=160)
    exprs = [ir.parse(s) for s in _MIXED_TRIPLE]
    mesh = make_mesh()
    STRUCTURAL.shard_spans = True
    try:
        assert _check_bucketed(entries, exprs, packed=False,
                               mesh=mesh) == 3
    finally:
        STRUCTURAL.shard_spans = False


def test_mixed_plans_without_shared_bucket_still_raise():
    """stack_queries keeps its caller-bug contract: a mixed group whose
    members do NOT canonicalize into one bucket raises rather than
    silently dropping a predicate."""
    entries = _corpus(51, n=40)
    blocks = [ColumnarPages.build(entries, E_GEO)]
    eng = MultiBlockEngine(top_k=128)
    batch = eng.stage(blocks)
    mqs = []
    for src in (_MIXED_TRIPLE[0],
                '{"tag": {"k": "env", "v": "prod"}}'):  # different bucket
        expr = ir.parse(src)
        req = _mk_req(expr)
        mq = compile_multi(blocks, req, cache_on=batch)
        mq.structural = compile_structural(expr, blocks, cache_on=batch)
        mqs.append(mq)
    with pytest.raises(ValueError, match="bucket"):
        stack_queries(mqs)


# ------------------------------------------------- serving path


def test_serving_path_fuses_mixed_plan_queries(tmp_path):
    """8 concurrent MIXED-plan structural searches through the full
    serving path fuse under the bucket gate: byte-identical to serial,
    result=stacked_bucketed booked, and /debug/scan shows per-bucket
    stack ratios + occupancy."""
    from tempo_tpu.observability import metrics as obs

    entries = _corpus(61, n=120)
    db = _mkdb(tmp_path, entries,
               search_structural_stack_enabled=True,
               search_structural_bucket_enabled=True,
               search_coalesce_window_s=0.05)
    assert STRUCTURAL.bucket_enabled is True
    srcs = [_MIXED_TRIPLE[i % 3] for i in range(8)]
    exprs = [ir.parse(s) for s in srcs]
    # reparam the repeats so every request is a distinct query while
    # the SHAPES still span >= 3 distinct plans in one bucket
    rng = random.Random(7)
    exprs = [e if i < 3 else _reparam(exprs[i % 3], rng)
             for i, e in enumerate(exprs)]

    def canon(resp):
        resp.metrics.device_seconds = 0
        return resp.SerializeToString()

    serial = []
    for e in exprs:
        r = _mk_req(e, limit=1000)
        serial.append(canon(db.search("t", r).response()))
    co = db.batcher.coalescer
    base_bucketed = co.structural_bucketed
    ev0 = obs.structural_stack_events.value(result="stacked_bucketed")
    out = [None] * len(exprs)
    barrier = threading.Barrier(len(exprs))

    def one(i):
        r = _mk_req(exprs[i], limit=1000)
        barrier.wait()
        out[i] = canon(db.search("t", r).response())

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(exprs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(len(exprs)):
        assert out[i] == serial[i], f"query {i} diverged under bucketing"
    assert co.structural_bucketed > base_bucketed, "no bucketed fusion"
    assert obs.structural_stack_events.value(
        result="stacked_bucketed") > ev0
    stats = co.stats()
    assert stats["structural_bucketed"] > 0
    assert stats["buckets"], "per-bucket stats missing"
    row = next(iter(stats["buckets"].values()))
    assert row["stack_ratio"] > 1
    assert 0 < row["occupancy"] <= 1
    dbg = db.batcher.debug_stats()
    assert dbg["coalesce"]["structural_bucketed"] \
        == co.structural_bucketed
