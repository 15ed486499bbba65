"""One scan program (search/multiblock.py `batch_scan_kernel`), four
launch kinds: solo and fused, on one device and on a mesh. What the
entry is given decides what it traces, so two things are pinned here:
the four answer alike (bit-equal, and equal to the per-entry oracle
`search.data.search_data_matches`), and each kind's trace has the shape
its launch needs and no more — no shard_map off a mesh, no query axis
on a solo launch. Plus the offline harness (`cli/blocks.py search`),
which answers through the batcher like the server."""

import functools
import json

import jax
import numpy as np
import pytest

from tempo_tpu.parallel import make_mesh
from tempo_tpu.search import dict_probe, multiblock, pipeline
from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
from tempo_tpu.search.data import search_data_matches
from tempo_tpu.search.engine import fetch_scan_out
from tempo_tpu.search.multiblock import (
    MultiBlockEngine,
    batch_scan_kernel,
    compile_multi,
    stack_queries,
    unpack_queries,
)

from tests.test_fused_params import kernel_calls  # noqa: F401 (fixture)
from tests.test_search import _corpus, _mk_req

KINDS = ("solo", "fused", "mesh_solo", "mesh_fused")
TOP_K = 256
GEOMETRY = PageGeometry(32, 8)


def _blocks():
    per_block = [_corpus(150, seed=s) for s in (3, 5, 8)]
    return ([sd for entries in per_block for sd in entries],
            [ColumnarPages.build(entries, GEOMETRY)
             for entries in per_block])


def _reqs():
    reqs = [
        _mk_req({"service.name": "frontend"}),
        _mk_req({"http.status_code": "500", "region": "us"},
                min_duration_ms=2_000),
        _mk_req({"service.name": "cart"}, max_duration_ms=9_000,
                start=1_600_000_010, end=1_600_000_120),
    ]
    for r in reqs:
        r.limit = 200
    return reqs


def _launch(kind, blocks, reqs):
    """[(count, inspected, scores, idx)] per request, by launch kind."""
    eng = MultiBlockEngine(
        top_k=TOP_K, mesh=make_mesh(4) if kind.startswith("mesh") else None)
    batch = eng.stage(blocks)
    mqs = [compile_multi(blocks, r, cache_on=batch) for r in reqs]
    if kind.endswith("solo"):
        return eng, batch, mqs, [eng.scan(batch, mq) for mq in mqs]
    counts, inspected, scores, idx = fetch_scan_out(
        eng.coalesced_scan_async(batch, stack_queries(mqs), TOP_K))
    # a fused launch pads its query axis to a power of two: dead lanes
    assert counts.shape[0] == 4 and counts[3] == 0
    return eng, batch, mqs, [
        (int(counts[i]), inspected, scores[i], idx[i])
        for i in range(len(reqs))]


@pytest.mark.parametrize("kind", KINDS)
def test_launch_kinds_answer_alike_and_as_the_oracle(kind):
    """Count, scores and flat indices bit-equal to the plain solo
    launch's, for every request; matches equal to the oracle's."""
    entries, blocks = _blocks()
    reqs = _reqs()
    _, _, _, want = _launch("solo", blocks, reqs)
    eng, batch, mqs, got = _launch(kind, blocks, reqs)
    for req, mq, w, g in zip(reqs, mqs, want, got):
        assert g[0] == w[0] and g[1] == w[1] == len(entries)
        np.testing.assert_array_equal(g[2], w[2])
        np.testing.assert_array_equal(g[3], w[3])
        expected = {sd.trace_id for sd in entries
                    if search_data_matches(sd, req)}
        assert expected and g[0] == len(expected)
        ids = {bytes.fromhex(m.trace_id)
               for m in eng.results(batch, mq, g[2], g[3])}
        assert ids == expected


def _parent_form(tables, statics):
    """What the scan program returned until PR 41, for the operands a
    launch of today was given: the (count, inspected, scores, idx)
    TUPLE of `_scan_pages` over the whole page axis on one device,
    jitted as the parent's entry jitted it, fetched part by part."""
    host = [None if t is None else np.asarray(t) for t in tables]
    queries = host[7:14]
    if statics["packed"] is not None:
        queries = unpack_queries(host[7], statics["packed"])
    scan = jax.jit(functools.partial(
        multiblock._scan_pages, n_terms=statics["n_terms"],
        top_k=statics["top_k"], widths=statics["widths"], plan=None,
        agg=None))
    count, inspected, scores, idx = scan(
        *host[:7], *queries, host[14], host[15], host[16], None, None,
        None, None)
    return (np.asarray(count), int(inspected), np.asarray(scores),
            np.asarray(idx))


@pytest.mark.parametrize("kind", KINDS + ("mask_solo", "mask_fused"))
def test_the_one_array_holds_what_the_parent_tuple_held(
        kind, kernel_calls, monkeypatch):  # noqa: F811
    """Every trace of the program, and `mask_scan_kernel` (the same
    body given hit masks), solo and fused: the one int32 array it
    returns, read by `unpack_out`, equals bit for bit the four arrays
    the parent's program returned for the same operands."""
    _, blocks = _blocks()
    reqs = _reqs()
    mask = kind.startswith("mask")
    if mask:
        # every device probe's product leaves as a hit mask
        monkeypatch.setattr(dict_probe, "R_MAX", 0)
        pipeline._COMPILE_CACHE.clear()
    eng = MultiBlockEngine(
        top_k=TOP_K, mesh=make_mesh(4) if kind.startswith("mesh") else None,
        device_probe_min_vals=1 if mask else None)
    batch = eng.stage(blocks)
    mqs = [compile_multi(blocks, r, cache_on=batch) for r in reqs]
    if mask:
        pipeline._COMPILE_CACHE.clear()
    assert all((mq.val_hits is not None) == mask for mq in mqs)
    if kind.endswith("solo"):
        got = [eng.scan(batch, mq) for mq in mqs]
    else:
        counts, inspected, scores, idx = fetch_scan_out(
            eng.coalesced_scan_async(batch, stack_queries(mqs), TOP_K))
        got = [(counts, inspected, scores, idx)]
    assert len(kernel_calls) == len(got)
    assert any(np.any(g[0]) for g in got)
    for (tables, statics, name), g in zip(kernel_calls, got):
        assert name == ("mask_scan_kernel" if mask else "batch_scan_kernel")
        want = _parent_form(tables, statics)
        assert g[1] == want[1] and type(g[1]) is int
        for a, b in zip((g[0], g[2], g[3]), (want[0], want[2], want[3])):
            # (a solo launch's count comes back a Python int)
            assert b.dtype == getattr(a, "dtype", np.int32) == np.int32
            np.testing.assert_array_equal(a, b)


def _avals(jaxpr):
    """Every value's abstract shape, sub-jaxprs (pjit, while, shard_map,
    vmapped bodies) included."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield eqn.primitive.name, tuple(getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


@pytest.mark.parametrize("kind", KINDS)
def test_each_kind_traces_what_its_launch_needs_and_no_more(kind):
    """The solo launch has no query axis (not even one of size 1), the
    fused launch carries [Q, ...]; off a mesh there is no shard_map,
    on one the page axis is split under it. Sp2 measured the query-axis
    body at 4.4x the solo body on the chip (PERF.md section 6, PR 25):
    folding the kinds into one trace would be a regression no CPU test
    of answers can see."""
    _, blocks = _blocks()
    reqs = _reqs()[:2]
    mesh = make_mesh(4) if kind.startswith("mesh") else None
    eng = MultiBlockEngine(top_k=TOP_K, mesh=mesh)
    d = eng.stage(blocks).device
    mqs = [compile_multi(blocks, r) for r in reqs]
    P, E, C = d["kv_key"].shape
    if kind.endswith("solo"):
        mq, Q = mqs[0], None
        tables = (mq.term_keys, mq.val_ranges, None, np.uint32(mq.dur_lo),
                  np.uint32(min(mq.dur_hi, 0xFFFFFFFF)),
                  np.uint32(mq.win_start),
                  np.uint32(min(mq.win_end, 0xFFFFFFFF)))
        n_terms = mq.n_terms
    else:
        cq = stack_queries(mqs)
        Q = cq.term_keys.shape[0]
        tables = (cq.term_keys, cq.val_ranges, cq.term_active, cq.dur_lo,
                  cq.dur_hi, cq.win_start, cq.win_end)
        n_terms = cq.n_terms

    def launch(*args):
        return batch_scan_kernel(*args, mesh=mesh, n_terms=n_terms,
                                 top_k=TOP_K)

    closed = jax.make_jaxpr(launch)(
        d["kv_key"], d["kv_val"], d["entry_start"], d["entry_end"],
        d["entry_dur"], d["entry_valid"], d["page_block"], *tables)
    prims = {name for name, _ in _avals(closed.jaxpr)}
    shapes = {shape for _, shape in _avals(closed.jaxpr)}

    assert ("shard_map" in prims) == (mesh is not None)
    local = P // 4 if mesh is not None else P
    assert (local, E) in shapes and (local, E, C) in shapes
    if mesh is not None:
        # the page arrays are split, never gathered whole inside
        assert (P, E, C) not in shapes
        assert {"psum", "all_gather"} <= prims
    else:
        assert not {"psum", "all_gather", "axis_index"} & prims
    # ONE int32 output, packed inside the shard_map on a mesh: a row
    # of count, inspected, scores [k], idx [k], and a row a member
    (out,) = closed.jaxpr.outvars
    assert str(out.aval.dtype) == "int32"
    if Q is None:
        assert out.aval.shape == (2 + 2 * TOP_K,)
        assert not any(s[:1] == (1,) and s[1:3] == (local, E)
                       for s in shapes)
    else:
        assert out.aval.shape == (Q, 2 + 2 * TOP_K)
        assert (Q, local, E) in shapes


def test_cli_search_answers_through_the_batcher(tmp_path, capsys):
    """`tempo-tpu-cli search`: every block a ScanJob through
    BlockBatcher.search (breaker, ownership and host fallback from the
    one place that has them), the answer the oracle's."""
    from tempo_tpu.backend import BlockMeta
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.cli import blocks as cli
    from tempo_tpu.observability import metrics as obs
    from tempo_tpu.search import write_search_block

    be = LocalBackend(str(tmp_path))
    per_block = [_corpus(90, seed=s) for s in (1, 2)]
    for entries in per_block:
        write_search_block(be, BlockMeta(tenant_id="t1"), entries, GEOMETRY)
    req = _mk_req({"service.name": "checkout"}, min_duration_ms=1_000)
    expected = {sd.trace_id.hex() for entries in per_block for sd in entries
                if search_data_matches(sd, req)}
    assert expected

    before = {m: obs.scan_dispatches.value(mode=m)
              for m in ("batched", "host_fallback")}
    assert cli.main(["--backend-path", str(tmp_path), "search", "t1",
                     "--tags", "service.name=checkout",
                     "--min-duration", "1s", "--limit", "500"]) == 0
    resp = json.loads(capsys.readouterr().out)
    assert {t["traceId"] for t in resp["traces"]} == expected
    assert int(resp["metrics"]["inspectedTraces"]) == 180
    assert int(resp["metrics"]["inspectedBlocks"]) == 2
    assert obs.scan_dispatches.value(mode="batched") > before["batched"]
    assert obs.scan_dispatches.value(mode="host_fallback") \
        == before["host_fallback"]
