"""Op `search_red`: GET /api/search?agg=red: the RED view of a tenant
(rate, errors, duration by service) asked of the stored traces under a
filter: what a Grafana service-overview or Explore-Traces panel sends a
tracing backend (upstream TraceQL metrics: `{ <filter> } | rate() by
(resource.service.name)`, `| histogram_over_time(duration) by (...)`), in
this fork's form (docs/search-analytics.md).

Everything but `agg=red` is op `search`'s: the template parameters
(`tags` with `draw` / `fixed`, `min_duration_quantile`, `window_s`,
`limit`, `variants`; see chipbench/ops/search.py), the values it draws
from the seed and what the harness may sum (`work`: `inspected_entries`).
`exhaustive` is not taken: an aggregating search skips blocks by the
header rollup as a plain one does.

`check` holds an answer to `chipbench/reference_red.py`, exactly: the
trace list as `ops/search.py` holds a search that ran to the end
(`inspectedTraces`, and the match set or the latest `limit` starts), then
`aggregates`: `type`, `buckets_ms`, and every service's `calls`, `errors`
and fifteen bins equal as integers, a service with no match absent on
both sides.

The op's name starts with `search`, which `lib.count_ok` and
`lib.latencies_ms` match on.

A program from before PR 48 serves these requests too (the reduction is
older than its counters and than its place in the HBM budget) and is
driven like any other: PR 48's parent, with these files laid over it,
ran the cell on a v5e inside its limits (`PARENT_RUN`), so it is the
cell's baseline and nothing here refuses it. The readers of
`tempo_search_agg_*` and of the `analytics.decode` / `results.merge_agg`
spans return nothing there.
"""

from __future__ import annotations

import base64
import json

from chipbench import reference, reference_red
from chipbench.ops import search
from chipbench.ops.search import work  # noqa: F401

# what PR 48's parent did under this cell's traffic on a v5e (my chip
# run, PR 48; PERF.md section 6)
PARENT_RUN = (
    "two runs, seeds 2147485001 and 2147486001, every answer equal to the "
    "reference, `correct: true`, 0 failed: 465 and 450 searches a window, "
    "`scan_rate` 440.3 and 413.7 M entries/s, `setup_s` 143.4 and 134.4 with "
    "its jit keys replayed, a run ~230 s of the 360 it may take; it fuses "
    "aggregating members, so one of the two windows met a fused shape cold "
    "(1 jit miss; replayed from the persistent cache that time)")


def build(params: dict, manifest: dict, rng) -> list[dict]:
    if params.get("exhaustive"):
        raise ValueError("op search_red takes no `exhaustive`")
    out = search.build(params, manifest, rng)
    for r in out:
        r["path"] += "&agg=red"
    return out


def _expect(request: dict, manifest: dict) -> dict:
    """The reference's answer, kept in op `search`'s own memo: its
    `check` then holds the trace list to this answer, a search that ran
    to the end."""
    memo = manifest.setdefault("_search_reference", {})
    key = json.dumps(request["ref"], sort_keys=True)
    if key not in memo:
        memo[key] = (
            reference_red.answer(request["ref"], manifest,
                                 manifest.get("_pool")),
            reference._terms(request["ref"], manifest))
    return memo[key][0]


def check(request: dict, response: dict, manifest: dict):
    """(ok, detail). Exact comparisons only: limit 0 on every number."""
    want = _expect(request, manifest)
    ok, why = search.check(request, response, manifest)
    if not ok:
        return ok, why
    doc = json.loads(base64.b64decode(response["body"]))
    got = doc.get("aggregates")
    if not isinstance(got, dict):
        return False, "no aggregates in the answer"
    ref = want["aggregates"]
    for k in ("type", "buckets_ms"):
        if got.get(k) != ref[k]:
            return False, f"aggregates.{k} {got.get(k)!r} != {ref[k]!r}"
    series = got.get("series")
    if not isinstance(series, dict):
        return False, "aggregates.series is no object"
    if sorted(series) != sorted(ref["series"]):
        odd = sorted(set(series) ^ set(ref["series"]))
        return False, (f"{len(series)} services, reference has "
                       f"{len(ref['series'])}; e.g. {odd[:3]}")
    for svc, r in ref["series"].items():
        g = series[svc]
        for k in ("calls", "errors", "hist"):
            if g.get(k) != r[k] or not _all_ints(g.get(k)):
                return False, (f"{svc}.{k} {g.get(k)!r} != reference "
                               f"{r[k]!r}")
    return True, ""


def _all_ints(v) -> bool:
    vs = v if isinstance(v, list) else [v]
    return all(type(x) is int for x in vs)
