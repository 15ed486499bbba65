"""The cell of many tenants: its readers, each on a hand-made run with
the value worked by hand and on a program that lacks what it reads; the
generator's tenant sizes, classes and per-tenant views; the pools of op
`search_tenant`; and the rehearsal of `tenants32.scan`."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench.generators import otel_blocks, otel_tenants
from chipbench.tests.test_span_layers import EMPTY, Spans, reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ROWS = "tempo_search_launch_table_rows_total"
KEYS = "tempo_search_scan_jit_keys"
STAGE = "tempo_search_dispatch_stage_seconds"
DISPATCHES = "tempo_search_scan_dispatches_total"
SIZES = [177, 83, 53, 39, 30, 25, 21, 18, 16, 14, 13, 11, 11, 10, 9, 8, 8,
         7, 7, 7, 6, 6, 6, 5, 5, 5, 5, 4, 4, 4, 4, 4]


@pytest.fixture
def run():
    """Four tenants of 8, 5, 4 and 3 blocks of 4 pages, a group each; a
    window of 10 s whose middle 2 s were traced. Ten searches completed
    on 20 launches, 4 of them fused and serving 10 queries, `d2h` 60 ms
    over 12 syncs; their tables carried 300 real rows and 100 pad rows;
    40 jit keys at the end. The traced seconds saw 10 scan programs
    (1 ms) and four launches' spans: two on the group of 8 blocks, two
    on the group of 3. Over the window the groups of 8, 5 and 3 blocks
    were launched on (32, 32 and 16 staged pages); the group of 4 never."""
    s = Spans()
    s.add("batcher.Search", 0, 10_000, tenant="t1", groups=1)
    s.add("dispatch.execute", 100, 101, blocks=5, blocks_bucket=8)
    for at, blocks, bucket in ((4100, 8, None), (4500, 8, None),
                               (5200, 3, 4), (5900, 3, 4)):
        attrs = {"blocks": blocks}
        if bucket:
            attrs["blocks_bucket"] = bucket
        s.add("dispatch.execute", at, at + 1, **attrs)
    s.add("dispatch.build", 4090, 4100)            # no `blocks`: no launch
    for at, blocks, pages in ((90, 5, 32), (4090, 8, 32), (5190, 3, 16)):
        s.add("coalescer.launch", at, at + 12, blocks=blocks, shards=1,
              pages_per_shard=pages, queries=1)
    present = np.zeros((20, 13_000), dtype=bool)
    present[:, :9_000] = True
    one = '{mode="%s",shards="1"}'
    return {
        "trace": {"window_ns": 2e9,
                  "programs_ns": {"jit_batch_scan_kernel": 1e6,
                                  "jit_probe_kernel": 5e6},
                  "program_calls": {"jit_batch_scan_kernel": 10,
                                    "jit_probe_kernel": 4}},
        "spans": s.out, "device_kind": "TPU v5 lite",
        "config": {"chips": {"count": 1}},
        "manifest": {"pages": 80, "kv_per_entry": 16,
                     "block_ids": [str(i) for i in range(20)],
                     "group_blocks": [8, 5, 4, 3],
                     "key_names": tuple(f"k{i}" for i in range(16)),
                     "present": present},
        "requests": [{"op": "search_tenant"}],
        "records": [{"i": 0, "status": 200}] * 10,
        "counters": {
            "before": {
                ROWS: {'{kind="real"}': 100.0, '{kind="pad"}': 20.0},
                KEYS: {"": 38.0},
                DISPATCHES: {one % "batched": 5.0, one % "coalesced": 1.0},
                "tempo_search_coalesced_queries_total": {"": 2.0},
                STAGE + "_sum": {'{stage="d2h"}': 1.0},
                STAGE + "_count": {'{stage="d2h"}': 6.0}},
            "after": {
                ROWS: {'{kind="real"}': 400.0, '{kind="pad"}': 120.0},
                KEYS: {"": 40.0},
                DISPATCHES: {one % "batched": 21.0, one % "coalesced": 5.0},
                "tempo_search_coalesced_queries_total": {"": 12.0},
                STAGE + "_sum": {'{stage="d2h"}': 1.06},
                STAGE + "_count": {'{stage="d2h"}': 18.0}}},
    }


# a launch's real pages, the mean of (8, 8, 3, 3) blocks x 4 pages = 22,
# x 1,024 entries x (16 x (1 + 2) + 13) B, ten launches, over 819 GB/s,
# over 1 ms
ROOFLINE = 100.0 * (10 * 22 * 1024 * 61 / 819e9) / 1e-3
WANT = {
    "kernel_ms.tenants": 0.1,
    "tenants_kernel_roofline": ROOFLINE,
    "pad_row_share.tenants": 25.0,
    # groups of 8, 5 and 3 blocks: 64 real pages in 32 + 32 + 16 staged
    "pad_page_share.tenants": 20.0,
    "jit_keys.tenants": 40.0,
    "launches_per_search.tenants": 2.0,
    "searches_per_dispatch.tenants": 1.3,
    "sync_ms.tenants": 5.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_tenants_reader_on_a_run_that_exercises_it(run, name):
    assert reader(name)(run) == pytest.approx(WANT[name])


def test_the_roofline_counts_real_pages_of_the_traced_launches_alone(run):
    """Pad pages are no bytes: a launch on 3 blocks counts 12 pages, not
    the 16 staged; a launch outside the traced seconds counts nothing;
    without a trace window every launch of the window counts."""
    from chipbench import costs

    assert costs.scan_bytes(22, 16, 16, 9_000) == 22 * 1024 * 61
    assert ROOFLINE < 100.0 * (10 * 32 * 1024 * 61 / 819e9) / 1e-3
    run["trace"] = dict(run["trace"], window_ns=0)
    # (5 + 8 + 8 + 3 + 3) / 5 blocks x 4 pages = 21.6 pages a launch
    assert reader("tenants_kernel_roofline")(run) == pytest.approx(
        ROOFLINE * 21.6 / 22)


@pytest.mark.parametrize("name", sorted(WANT))
def test_tenants_reader_finds_nothing_and_says_so(run, name):
    """No spans, counters or trace at all; then this PR's parent: the
    launch spans and the dispatch counters, but no `blocks` on
    `dispatch.execute`, no row counter and no jit-key gauge."""
    assert reader(name)(dict(EMPTY, config={}, trace=None, records=[],
                             requests=[], manifest=run["manifest"])) is None
    parent = dict(run, spans=[
        dict(s, attributes={} if s["name"] == "dispatch.execute"
             else s["attributes"]) for s in run["spans"]])
    parent["counters"] = {
        side: {k: v for k, v in c.items() if k not in (ROWS, KEYS)}
        for side, c in run["counters"].items()}
    got = reader(name)(parent)
    if name in ("tenants_kernel_roofline", "pad_row_share.tenants",
                "jit_keys.tenants"):
        assert got is None
    else:
        assert got == pytest.approx(WANT[name])


def test_every_tenants_metric_is_registered_for_its_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in WANT:
        assert by_name[name]["workloads"] == ["tenants32.scan"]
        assert by_name[name]["moves"] == "scan_rate"
    for name, m in by_name.items():
        if name not in WANT:
            assert "tenants32.scan" not in m["workloads"], name
    (cell,) = [w for w in bench["workloads"] if w["name"] == "tenants32.scan"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tempo-search-tenants32", "tenants", 1)
    (rate,) = [m for m in bench["end_to_end"] if m["name"] == "scan_rate"]
    assert "tenants32.scan" in rate["workloads"]


def _conf(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


def test_the_configuration_is_share16s_held_by_32_tenants():
    conf, base = _conf("tempo-search-tenants32"), _conf("tempo-search-share16")
    assert conf["yaml"] == base["yaml"]
    same = {k: v for k, v in base["corpus"].items()
            if k not in ("generator", "tenant")}
    assert {k: conf["corpus"][k] for k in same} == same
    assert conf["corpus"]["tenants"] == 32
    assert conf["corpus"]["tenant_blocks"] == SIZES
    assert sorted(conf["reduced"]) == ["blocks", "entries_per_block"]
    assert len(conf["guarantees"]) == 3
    assert conf["chips"]["count"] == 1


# ---- the generator


def test_tenant_sizes_follow_the_law_and_sum_to_the_blocks():
    sizes = otel_tenants.tenant_sizes(625, 32, 1.1)
    assert sizes == SIZES
    assert sum(sizes) == 625 and min(sizes) == 4 and len(sizes) == 32
    whole = otel_tenants.tenant_sizes(10_000, 32, 1.1)
    assert (whole[0], whole[-1], sum(whole)) == (2_837, 63, 10_000)
    # 35 groups of 20 distinct block counts, five block-axis buckets
    groups = [g for n in sizes for g in otel_tenants.group_sizes(n, 64)]
    assert len(groups) == 35 and len(set(groups)) == 20
    assert groups[:5] == [64, 64, 49, 64, 19]
    assert {otel_tenants._pow2(g) for g in groups} == {4, 8, 16, 32, 64}
    classes = [otel_tenants.last_group_bucket(n, 64) for n in sizes]
    assert classes[:8] == [64, 32, 64, 64, 32, 32, 32, 32]
    assert [classes.count(c) for c in (64, 32, 16, 8, 4)] == [3, 5, 7, 12, 5]
    assert otel_tenants.tenant_sizes(12, 4, 1.1) == [6, 3, 2, 1]


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    from concurrent.futures import ThreadPoolExecutor

    conf = _conf("tempo-search-tenants32")
    corpus = {**conf["corpus"], **conf["tiny"]["corpus"], "config_name": "t",
              "entries_per_block": 1024}
    with ThreadPoolExecutor(2) as pool:
        return otel_tenants.generate(
            corpus, 2**31 + 9, str(tmp_path_factory.mktemp("tn")), pool)


def test_the_generator_refuses_sizes_that_are_not_the_laws(tmp_path):
    conf = _conf("tempo-search-tenants32")
    corpus = {**conf["corpus"], **conf["tiny"]["corpus"], "config_name": "t",
              "tenant_blocks": [5, 4, 2, 1]}
    with pytest.raises(ValueError, match=r"\[6, 3, 2, 1\]"):
        otel_tenants.generate(corpus, 1, str(tmp_path), None)
    assert not os.listdir(tmp_path)


def test_blocks_are_tenant_major_and_a_view_is_one_tenants(manifest):
    m = manifest
    assert m["tenants"] == ["t1", "t2", "t3", "t4"]
    assert m["blocks"] == {"t1": 6, "t2": 3, "t3": 2, "t4": 1}
    assert m["tenant_slice"]["t2"] == (6, 9)
    assert m["group_blocks"] == [6, 3, 2, 1]
    assert m["tenant_class"] == {"t1": 8, "t2": 4, "t3": 2, "t4": 1}
    assert m["vals"].shape[0] == 12 and len(set(m["block_ids"])) == 12
    v = otel_tenants.view(m, "t2")
    assert v["vals"].shape[0] == 3 and v["vals"].base is not None
    assert np.array_equal(v["start"], m["start"][6:9])
    assert otel_tenants.view(m, "t2") is v
    ids = otel_blocks.trace_ids(7, 1)
    own = bytes(ids[0, 5]).hex()
    other = bytes(otel_blocks.trace_ids(2, 1)[0, 5]).hex()
    assert v["entry_of_trace_id"](own) == (1, 5)
    assert v["entry_of_trace_id"](other) is None
    # no two tenants' blocks hold the same entries, and each tenant's
    # blocks cover its own day
    assert not np.array_equal(m["vals"][0], m["vals"][6])
    for t, (lo, hi) in m["tenant_slice"].items():
        span = m["end"][lo:hi].max() - m["start"][lo:hi].min()
        assert 0.9 * 86_400 < span < 1.25 * 86_400, t


# ---- the op


def _requests(manifest, seed, scale="tiny"):
    from chipbench import run as harness
    from chipbench.server import merge

    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "tenants.json")) as f:
        traffic = json.load(f)
    if scale == "tiny":
        traffic = merge(traffic, traffic["tiny"])
    return harness.build_requests(traffic, manifest, seed)


def _described(sizes, n_pages=64):
    """A manifest of the full configuration's tenants without its data:
    all that op `search_tenant` reads to build its pools."""
    conf = _conf("tempo-search-tenants32")["corpus"]
    vocab = otel_blocks.vocabulary(conf)
    tenants = otel_tenants.tenant_ids({"tenants": len(sizes)})
    return {
        "tenant": tenants[0], "tenants": tenants,
        "tenant_law": otel_blocks._zipf(len(sizes), 1.1).tolist(),
        "tenant_class": {t: otel_tenants.last_group_bucket(n, n_pages)
                         for t, n in zip(tenants, sizes)},
        "vocab": {"services": vocab["services"],
                  "domains": {k: (vals, None if p is None else p.tolist())
                              for k, (vals, p) in vocab["domains"].items()}},
        "dur_ms_quantile": lambda q: otel_blocks.duration_ms_quantile(
            conf, float(q)),
        "time_base": conf["time_base"], "time_span_s": conf["time_span_s"],
    }


def test_pools_are_tenant_major_one_class_each_and_64_requests_a_seed():
    m = _described(SIZES)
    reqs, ops = _requests(m, 2**31 + 5, scale="full")
    again, _ = _requests(m, 2**31 + 5, scale="full")
    other, _ = _requests(m, 11, scale="full")
    key = lambda r: (r["headers"]["X-Scope-OrgID"], r["path"])  # noqa: E731
    assert [key(r) for r in reqs] == [key(r) for r in again]
    assert {key(r) for r in reqs} != {key(r) for r in other}
    assert [len(o["pool"]) for o in ops] == [16, 11, 5, 5, 1, 11, 7, 4, 3, 1]
    # strata of the services' law: its head takes several of them, so a
    # few requests of a seed repeat a tenant's predicate
    assert len(reqs) == 64 and len({key(r) for r in reqs}) >= 56
    assert sum(o["share"] for o in ops) == pytest.approx(1.0, abs=1e-3)
    for o, want in zip(ops, (64, 32, 16, 8, 4) * 2):
        ranks = [reqs[i]["tenant_rank"] for i in o["pool"]]
        assert ranks == sorted(ranks)
        assert {m["tenant_class"][m["tenants"][r]] for r in ranks} == {want}
    # the first burst of the heaviest class lands on the heaviest tenant
    assert [reqs[i]["tenant_rank"] for i in ops[0]["pool"][:8]] == [0] * 8
    # the heaviest tenant is asked as often as the law has it: 28 %
    share = np.mean([
        np.mean([r["tenant_rank"] == 0
                 for r in _requests(m, s, scale="full")[0]])
        for s in range(20)])
    assert 0.24 < share < 0.33


def test_a_class_no_tenant_is_in_builds_no_request(manifest):
    reqs, ops = _requests(manifest, 3, scale="full")
    # the tiny corpus has classes 8, 4, 2, 1: of the full mix's five
    # classes only 8 and 4 are there
    assert [bool(o["pool"]) for o in ops] == [False, False, False, True,
                                              True] * 2
    reqs, ops = _requests(manifest, 3)
    assert all(o["pool"] for o in ops)
    assert {r["headers"]["X-Scope-OrgID"] for r in reqs} == set(
        manifest["tenants"])


def test_check_holds_an_answer_to_its_own_tenant(manifest, monkeypatch):
    from chipbench.ops import search, search_tenant

    seen = []
    monkeypatch.setattr(search, "check",
                        lambda req, resp, m: seen.append(m) or (True, ""))
    manifest["_pool"] = "the pool"
    try:
        for t in ("t3", "t1"):
            assert search_tenant.check(
                {"headers": {"X-Scope-OrgID": t}}, {}, manifest) == (True, "")
    finally:
        del manifest["_pool"]
    assert [m["tenant"] for m in seen] == ["t3", "t1"]
    assert [m["vals"].shape[0] for m in seen] == [2, 6]
    assert all(m["_pool"] == "the pool" for m in seen)


# ---- the rehearsal


def test_rehearsal_of_the_tenants_cell():
    """Every step of `tenants32.scan` at the tiny size: four tenants of
    6, 3, 2 and 1 blocks, every answer held to its tenant's reference.
    The CPU's profile has no device plane: the two `device_trace`
    readers find nothing here."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "tenants32.scan", "--seed", str(2**31 + 4000), "--seconds", "3",
         "--trace", "1", "--scale", "tiny"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=1500)
    lines = p.stdout.strip().splitlines()
    assert p.returncode == 3, p.stdout[-3000:] + p.stderr[-3000:]
    assert "REHEARSAL on cpu" in lines[-1]
    for name in WANT:
        if name not in ("kernel_ms.tenants", "tenants_kernel_roofline"):
            assert name in lines[-1], lines[-1]
    assert "mismatches=0 (limit 0)" in p.stdout
    assert "blocks={'t1': 6, 't2': 3, 't3': 2, 't4': 1}" in p.stdout
    assert "groups staged=4" in p.stdout
