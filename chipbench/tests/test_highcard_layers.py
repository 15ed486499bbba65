"""The cell of high-cardinality tags: its readers, each on a hand-made
run with the value worked by hand and on a program that lacks what it
reads; the generator's refusal of a corpus that straddles a power of
two; the op's and the generator's refusal of a program without the
membership counter; the needles of op `search_highcard`; and the
rehearsal of `highcard.substring`, which has to take the device path."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench.generators import otel_highcard
from chipbench.tests.test_span_layers import EMPTY, Spans, reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MEMBERS = "tempo_search_scan_membership_total"
PROBES = "tempo_search_dict_probes_total"
MEMO = "tempo_search_prepare_memo_total"
STAGE = "tempo_search_dispatch_stage_seconds"
DISPATCHES = "tempo_search_scan_dispatches_total"
MASK_PEAK = "tempo_search_probe_mask_peak_bytes"
GIB = 1 << 30
PROBE = '{mode="dict_probe",stage="execute"}'


@pytest.fixture
def run():
    """Ten searches completed. In the window 100 launch members, 5 of
    them by mask in 5 launches; 30 device probes and no host probe; 50
    memo lookups, 20 misses; since the start 1,000 device probes in 2 s.
    Searches spent 400 ms in the batcher, 100 of them in `prepare`. The
    cache holds 3 GiB, 1.2 of them dictionaries; masks peaked at 0.5 GiB
    against the shipped 4. Twelve launches, four fused and serving ten
    queries, `d2h` 60 ms together. The traced seconds saw 8 launches of
    the range program (16 ms) and 2 of the mask program (900 ms) over
    one group of 2 blocks x 4 pages x 16 slots, 53,000 values."""
    s = Spans()
    b = s.add("batcher.Search", 0, 300, groups=1)
    s.add("batcher.Search", 300, 400, groups=1)
    p = s.add("batcher.prepare", 10, 110, parent=b)
    s.add("dict_probe.probe", 11, 100, parent=p, path="device", device=2,
          host=0, cached=0, dicts=2, terms=1, runs_max=3, membership="mask")
    for _ in range(5):
        s.add("dispatch.execute", 120, 121, membership="mask")
    s.add("dispatch.execute", 130, 131, membership="range")
    present = np.zeros((2, 60_000), dtype=bool)
    present[:, :53_000] = True
    return {
        "trace": {"programs_ns": {"jit_batch_scan_kernel": 16e6,
                                  "jit_mask_scan_kernel": 900e6,
                                  "jit_probe_kernel": 5e6},
                  "program_calls": {"jit_batch_scan_kernel": 8,
                                    "jit_mask_scan_kernel": 2,
                                    "jit_probe_kernel": 4}},
        "spans": s.out, "device_kind": "TPU v5 lite",
        "config": {"yaml": {}},
        "manifest": {"pages": 8, "kv_per_entry": 16, "block_ids": ["a", "b"],
                     "key_names": tuple(f"k{i}" for i in range(16)),
                     "present": present},
        "requests": [{"op": "search_highcard"}],
        "records": [{"i": 0, "status": 200}] * 10,
        "counters": {
            "before": {
                MEMBERS: {'{path="range"}': 50.0, '{path="mask"}': 5.0},
                PROBES: {'{path="device"}': 970.0, '{path="cached"}': 10.0},
                MEMO: {'{result="hit"}': 10.0, '{result="miss"}': 10.0},
                DISPATCHES: {'{mode="batched"}': 5.0,
                             '{mode="coalesced"}': 1.0},
                "tempo_search_coalesced_queries_total": {"": 2.0},
                STAGE + "_sum": {'{stage="d2h"}': 1.0, PROBE: 1.9},
                STAGE + "_count": {'{stage="d2h"}': 6.0, PROBE: 970.0}},
            "after": {
                MEMBERS: {'{path="range"}': 145.0, '{path="mask"}': 10.0},
                PROBES: {'{path="device"}': 1000.0, '{path="cached"}': 90.0},
                MEMO: {'{result="hit"}': 40.0, '{result="miss"}': 30.0},
                DISPATCHES: {'{mode="batched"}': 13.0,
                             '{mode="coalesced"}': 5.0},
                "tempo_search_coalesced_queries_total": {"": 12.0},
                STAGE + "_sum": {'{stage="d2h"}': 1.06, PROBE: 2.0},
                STAGE + "_count": {'{stage="d2h"}': 18.0, PROBE: 1000.0},
                "tempo_search_hbm_cache_bytes": {"": 3.0 * GIB},
                "tempo_search_probe_dict_bytes": {"": 1.2 * GIB},
                MASK_PEAK: {"": 0.5 * GIB}}},
    }


# 8 pages x 1,024 entries x (16 x (1 + 4) + 13) B a launch, ten launches;
# the two mask launches also read one mask row a dictionary: 1 member x
# 2 dictionaries x 65,536 B; over 819 GB/s, over 0.916 s
COLUMNS = 8 * 1024 * 93
ROOFLINE = 100.0 * ((10 * COLUMNS + 2 * 2 * 65_536) / 819e9) / 0.916
WANT = {
    "kernel_ms.highcard": 91.6,
    "highcard_kernel_roofline": ROOFLINE,
    "probe_ms.highcard": 2.0,
    "probes_per_search.highcard": 3.0,
    "mask_launch_share.highcard": 5.0,
    "memo_miss_share.highcard": 40.0,
    "prepare_share.highcard": 25.0,
    "dict_hbm_share.highcard": 40.0,
    "mask_hbm_over_budget.highcard": 0.125,
    "searches_per_dispatch.highcard": 1.5,
    "sync_ms.highcard": 5.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_highcard_reader_on_a_run_that_exercises_it(run, name):
    assert reader(name)(run) == pytest.approx(WANT[name])


def test_no_share_of_the_roofline_counts_more_than_the_columns_and_masks():
    from chipbench import costs, costs_highcard

    assert costs_highcard.scan_bytes(8, 16, 16, 53_000) == COLUMNS
    assert costs_highcard.scan_bytes(8, 16, 16, 53_000) == costs.scan_bytes(
        8, 16, 16, 53_000)
    assert costs_highcard.scan_bytes(
        0, 0, 0, 53_000, mask_members=3, mask_dicts=64, terms=2) \
        == 3 * 64 * 2 * 65_536


@pytest.mark.parametrize("name", sorted(WANT))
def test_highcard_reader_finds_nothing_and_says_so(run, name):
    """No spans, counters or trace at all; then PR 33's parent: the
    launches, memo and stage counters, but no membership or probe
    counter, no mask gauge, no `dict_probe.probe` span, no `membership`
    on a launch, one scan program."""
    assert reader(name)(dict(EMPTY, config={}, trace=None, records=[],
                             requests=[])) is None
    parent = dict(run, spans=[
        dict(s, attributes={k: v for k, v in s["attributes"].items()
                            if k != "membership"})
        for s in run["spans"] if s["name"] != "dict_probe.probe"])
    parent["counters"] = {
        side: {k: v for k, v in c.items()
               if k not in (MEMBERS, PROBES, MASK_PEAK)}
        for side, c in run["counters"].items()}
    parent["trace"] = {"programs_ns": {"jit_batch_scan_kernel": 916e6},
                       "program_calls": {"jit_batch_scan_kernel": 10}}
    got = reader(name)(parent)
    if name in ("mask_launch_share.highcard", "probes_per_search.highcard",
                "mask_hbm_over_budget.highcard"):
        assert got is None
    else:
        assert got is not None


def test_every_highcard_metric_is_registered_for_its_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in WANT:
        assert by_name[name]["workloads"] == ["highcard.substring"]
        assert by_name[name]["moves"] == "scan_rate"
    for name, m in by_name.items():
        if name not in WANT:
            assert "highcard.substring" not in m["workloads"], name
    (cell,) = [w for w in bench["workloads"]
               if w["name"] == "highcard.substring"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tempo-search-highcard8", "highcard", 1)
    (rate,) = [m for m in bench["end_to_end"] if m["name"] == "scan_rate"]
    assert rate["workloads"][-1] == "highcard.substring"


def test_the_configuration_sets_nothing_of_the_program():
    conf, base = (json.load(open(os.path.join(
        ROOT, "chipbench", "configs", name + ".json")))
        for name in ("tempo-search-highcard8", "tempo-search-share16"))
    assert conf["yaml"] == base["yaml"]
    same = {k: v for k, v in base["corpus"].items()
            if k not in ("generator", "tenant", "blocks", "customers")}
    assert {k: conf["corpus"][k] for k in same} == same
    assert conf["corpus"]["customers"] == 1_250_000
    assert conf["corpus"]["min_vals"] == 50_000
    assert len(conf["guarantees"]) == 3
    assert "search_device_probe_min_vals" in conf["tiny"]["yaml"]["storage"]


# ---- the generator


def test_ids_are_distinct_well_formed_and_scattered():
    ids = otel_highcard.customer_ids(0, 200_000)
    assert len(set(ids)) == len(ids)
    assert all(len(i) == 11 and i.startswith("cus_") and i[4:].isalpha()
               and i[4:].islower() for i in ids[:2000])
    # any two letters after `cus_` open about 1/676 of the ids
    opened = sum(i.startswith("cus_qk") for i in ids)
    assert 0.6 < opened / (len(ids) / 676) < 1.4
    # a rank past the tenant's is an id no block holds
    assert otel_highcard.customer_ids(200_000, 200_001)[0] not in set(ids)


def test_the_generator_refuses_a_corpus_that_straddles_a_power_of_two():
    ok = otel_highcard.check_dictionaries([53_000, 53_400, 54_100], 50_000)
    assert "min=53000" in ok and "max=54100" in ok
    assert otel_highcard.check_dictionaries([65_536, 40_000])
    with pytest.raises(ValueError, match="straddle a power of two"):
        otel_highcard.check_dictionaries([65_000, 65_536, 65_537], 50_000)
    with pytest.raises(ValueError, match="floor of 50000"):
        otel_highcard.check_dictionaries([49_999, 53_000], 50_000)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    from concurrent.futures import ThreadPoolExecutor

    with open(os.path.join(ROOT, "chipbench", "configs",
                           "tempo-search-highcard8.json")) as f:
        conf = json.load(f)
    corpus = {**conf["corpus"], **conf["tiny"]["corpus"],
              "config_name": "t", "blocks": 3}
    with ThreadPoolExecutor(2) as pool:
        return otel_highcard.generate(
            corpus, 2**31 + 9, str(tmp_path_factory.mktemp("hc")), pool)


def _requests(manifest, seed):
    from chipbench import run as harness

    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "highcard.json")) as f:
        traffic = json.load(f)
    return harness.build_requests(traffic, manifest, seed)


def test_the_corpus_has_int32_ids_and_dictionaries_in_one_bucket(manifest):
    assert manifest["vals"].dtype == np.int32
    sizes = manifest["present"].sum(axis=1)
    assert (sizes - 1).max().item().bit_length() == (
        sizes - 1).min().item().bit_length()
    assert len(manifest["table"]) > 32_767


def test_needles_are_drawn_from_the_seed_by_kind(manifest):
    reqs, ops = _requests(manifest, 2**31 + 5)
    again, _ = _requests(manifest, 2**31 + 5)
    other, _ = _requests(manifest, 7)
    assert [r["path"] for r in reqs] == [r["path"] for r in again]
    assert {r["path"] for r in reqs} != {r["path"] for r in other}
    assert [len(o["pool"]) for o in ops] == [18, 12, 6, 2, 2]
    assert len({r["path"] for r in reqs}) == 40
    ids = set(manifest["vocab"]["domains"]["customer.id"][0])
    by = {}
    for r in reqs:
        by.setdefault(r["name"], []).append(r["ref"])
    for q in by["id-exact-slow"]:
        assert q["tags"]["customer.id"] in ids and q["min_ms"] > 0
    for q in by["id-prefix-errors-slow"]:
        n = q["tags"]["customer.id"]
        assert len(n) == 6 and n.startswith("cus_")
        assert q["tags"]["http.status_code"] == "500"
    for name, letters in (("id-fragment-slow", 3), ("id-fragment2-slow", 2)):
        for q in by[name]:
            n = q["tags"]["customer.id"]
            assert len(n) == letters and n.islower() and n not in "cus_"
            assert any(n in i[5:10] for i in ids)
    for q in by["id-absent"]:
        n = q["tags"]["customer.id"]
        assert len(n) == 11 and n.startswith("cus_") and n not in ids


def test_op_and_generator_refuse_a_program_without_the_counter(
        manifest, monkeypatch, tmp_path):
    """PR 33's parent has no `tempo_search_scan_membership_total`: the
    generator exits before it writes a block, and the op before the
    first request."""
    from tempo_tpu.observability.metrics import REGISTRY

    text = REGISTRY.expose()
    assert f"# TYPE {MEMBERS} " in text
    monkeypatch.setattr(
        REGISTRY, "expose", lambda: "\n".join(
            line for line in text.splitlines() if MEMBERS not in line))
    with pytest.raises(SystemExit, match=MEMBERS):
        _requests(manifest, 3)
    with pytest.raises(SystemExit, match=MEMBERS):
        otel_highcard.generate({}, 3, str(tmp_path), None)
    assert not os.listdir(tmp_path)


def test_the_op_holds_an_answer_to_no_host_probe(manifest, monkeypatch):
    from chipbench.ops import search, search_highcard
    from tempo_tpu.observability.metrics import REGISTRY

    monkeypatch.setattr(search, "check", lambda *a: (True, ""))
    text = "\n".join(line for line in REGISTRY.expose().splitlines()
                     if not line.startswith(PROBES))
    monkeypatch.setattr(REGISTRY, "expose", lambda: text)
    assert search_highcard.check({}, {}, {}) == (True, "")
    monkeypatch.setattr(REGISTRY, "expose",
                        lambda: text + f'\n{PROBES}{{path="host"}} 2\n')
    ok, why = search_highcard.check({}, {}, {})
    assert not ok and "2 host dictionary probes" in why


# ---- the rehearsal


def test_rehearsal_of_the_highcard_cell():
    """Every step of `highcard.substring` at the tiny size: 12 blocks
    whose dictionaries pass the floor the rehearsal lowers, so every
    probe runs on the device and none on the host (the op's check holds
    that). The CPU's profile has no device plane: the two `device_trace`
    readers find nothing here."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "highcard.substring", "--seed", str(2**31 + 3300), "--seconds", "3",
         "--trace", "1", "--scale", "tiny"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=1500)
    lines = p.stdout.strip().splitlines()
    assert p.returncode == 3, p.stdout[-3000:] + p.stderr[-3000:]
    assert "REHEARSAL on cpu" in lines[-1]
    for name in WANT:
        if name not in ("kernel_ms.highcard", "highcard_kernel_roofline"):
            assert name in lines[-1], lines[-1]
    assert "mismatches=0 (limit 0)" in p.stdout
    assert "otel_highcard: distinct values a block min=" in p.stdout
    assert "probe_dict_staged" not in p.stdout
