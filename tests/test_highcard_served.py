"""The served search path at a deployment's cardinality: two blocks of
65,536 entries whose dictionaries pass the shipped floor of 50,000
values for the device probe (and int16: value ids past 32,767 are
among them), asked through the HTTP handlers of one App with shipped
defaults and held to `chipbench/reference.py` by the benchmark's own
`check`: what the cell `highcard.substring` checks on the chip.
"""

import base64
import json
import urllib.parse
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from tempo_tpu.observability import metrics as obs
from tempo_tpu.search import dict_probe

CORPUS = {
    "generator": "otel_highcard", "tenant": "highcardtest",
    "config_name": "highcardtest", "blocks": 2, "entries_per_block": 65536,
    "services": 200, "routes": 500, "rpc_methods": 300, "pods": 2000,
    "customers": 1_250_000, "customer_zipf_s": 0.5,
    "min_vals": dict_probe.DEVICE_PROBE_MIN_VALS, "span_names": 400,
    "zipf_s": 1.1, "dur_median_ms": 40, "dur_sigma": 1.787,
    "time_base": 1700000000, "time_span_s": 86400, "time_overlap": 0.1,
}
# needle, other tags, minDuration quantile, and how its launches must
# have tested membership (None: every block pruned, nothing launched)
ASKS = {
    "exact": ({"kind": "exact"}, {}, None, "range"),
    "prefix-errors": ({"kind": "prefix", "letters": 2},
                      {"http.status_code": {"fixed": "500"}}, None, "range"),
    "fragment3-slow": ({"kind": "fragment", "letters": 3}, {}, "0.9",
                       "range"),
    # ~440 runs a dictionary: ranges, compared 64 at a time
    "fragment2-slow": ({"kind": "fragment", "letters": 2}, {}, "0.999",
                       "range"),
    "fragment1": ({"kind": "fragment", "letters": 1}, {}, "0.9999", "mask"),
    "absent": ({"kind": "absent"}, {}, None, None),
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from chipbench.generators import otel_highcard
    from tempo_tpu.db.tempodb import TempoDBConfig
    from tempo_tpu.modules import App, AppConfig
    from tempo_tpu.api import HTTPApi

    root = tmp_path_factory.mktemp("highcard")
    with ThreadPoolExecutor(2) as pool:
        manifest = otel_highcard.generate(CORPUS, 2**31 + 33,
                                          str(root / "blocks"), pool)
    app = App(AppConfig(
        backend={"backend": "local", "local": {"path": str(root / "blocks")}},
        wal_dir=str(root / "wal"), db=TempoDBConfig(auto_mesh=False)))
    app.poll_tick()
    manifest["_pool"] = None
    yield {"manifest": manifest, "api": HTTPApi(app, multitenancy=True)}
    app.shutdown()


def test_the_corpus_is_past_the_floor_and_past_int16(served):
    m = served["manifest"]
    sizes = m["present"].sum(axis=1)
    assert (sizes >= dict_probe.DEVICE_PROBE_MIN_VALS).all()
    assert (sizes <= 65_536).all()
    assert m["vals"].dtype == np.int32 and int(m["vals"].max()) > 32_767


@pytest.mark.parametrize("kind", sorted(ASKS))
def test_served_answers_equal_the_reference(served, kind):
    from chipbench.ops import search, search_highcard as op

    needle, tags, quantile, membership = ASKS[kind]
    params = {"op": "search_highcard", "needle": needle, "tags": tags,
              "variants": 2, "limit": 20}
    if quantile:
        params["min_duration_quantile"] = quantile
    m = served["manifest"]
    requests = op.build(params, m, np.random.default_rng(33))
    if kind == "exact":
        # two blocks hold a twelfth of the ids: ask for one they do hold,
        # a value id past int16 among them
        col = list(m["key_names"]).index("customer.id")
        held = m["vals"][1, col]
        vid = int(held[held > 32_767][0])
        requests += search.build(
            {"tags": {"customer.id": {"fixed": m["table"][vid]}}}, m,
            np.random.default_rng(33))
    before = {p: obs.scan_membership.value(path=p)
              for p in ("range", "mask")}
    probes = {p: obs.dict_probes.value(path=p) for p in ("host", "device")}
    for request in requests:
        path, _, qs = request["path"].partition("?")
        code, body = served["api"].handle(
            "GET", path, dict(urllib.parse.parse_qsl(qs)),
            request["headers"])
        # op `search`'s comparison: `op.check` adds a limit on a counter
        # that is the process's, and other tests share this process
        ok, why = search.check(request, {
            "status": code, "body": base64.b64encode(
                json.dumps(body).encode()).decode()}, m)
        assert ok, (request["path"], why)
    moved = {p: obs.scan_membership.value(path=p) - before[p]
             for p in before}
    other = {"range": "mask", "mask": "range"}
    if membership is None:
        assert moved == {"range": 0, "mask": 0}
    else:
        assert moved[membership] > 0 and moved[other[membership]] == 0
    # the shipped floor sends every dictionary of this tenant to the
    # device probe: none was probed on the host
    assert obs.dict_probes.value(path="host") == probes["host"]
    assert obs.dict_probes.value(path="device") > probes["device"]
