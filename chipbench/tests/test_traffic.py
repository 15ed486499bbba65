"""The traffic of a cell is a function of the seed: the same seed gives
the same requests, another seed other values by the same law, and the
triage mix sends more distinct predicates than the batcher memoises."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import run as harness  # noqa: E402
from chipbench.generators import otel_blocks  # noqa: E402


def _manifest():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "tempo-search-share16.json")) as f:
        corpus = json.load(f)["corpus"]
    v = otel_blocks.vocabulary(corpus)
    return {
        "tenant": "t", "time_base": corpus["time_base"],
        "time_span_s": corpus["time_span_s"],
        "dur_ms_quantile": lambda q: otel_blocks.duration_ms_quantile(
            corpus, float(q)),
        "vocab": {"services": v["services"], "roles": list(v["roles"]),
                  "domains": {k: (vals, None if p is None else p.tolist())
                              for k, (vals, p) in v["domains"].items()}}}


def _requests(mix, seed):
    with open(os.path.join(ROOT, "chipbench", "traffic", mix + ".json")) as f:
        traffic = json.load(f)
    return harness.build_requests(traffic, _manifest(), seed)


@pytest.mark.parametrize("mix,more_than", [("triage", 32), ("scan", 16)])
def test_requests_are_drawn_from_the_seed(mix, more_than):
    a, _ = _requests(mix, 2**31 + 5)
    b, _ = _requests(mix, 2**31 + 5)
    c, _ = _requests(mix, 7)
    assert [r["path"] for r in a] == [r["path"] for r in b]
    assert len(a) == len(c)
    assert {r["path"] for r in a} != {r["path"] for r in c}
    assert len({r["path"] for r in a}) > more_than
    assert len({r["path"] for r in c}) > more_than


def test_strata_follow_the_law():
    """Over many seeds a stratified pool has each service as often as
    the law says; in one seed the head is there as often as it is due."""
    reqs, ops = _requests("triage", 11)
    man = _manifest()
    vals, p = man["vocab"]["domains"]["service.name"]
    pool = [reqs[i]["ref"]["tags"]["service.name"] for i in ops[0]["pool"]]
    assert abs(pool.count(vals[0]) - p[0] * len(pool)) <= 1
    counts = dict.fromkeys(vals, 0)
    n = 0
    for seed in range(200):
        reqs, ops = _requests("triage", seed)
        for i in ops[0]["pool"]:
            counts[reqs[i]["ref"]["tags"]["service.name"]] += 1
            n += 1
    got = np.array([counts[v] for v in vals]) / n
    assert np.abs(got - np.asarray(p)).max() < 0.01
