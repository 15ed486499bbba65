"""Op `search`: GET /api/search.

`build(params, manifest, rng) -> requests` draws `variants` concrete
requests of one template from the seed's stream; `check(request,
response, manifest)` holds one answer to the plain reference
(`chipbench/reference.py`) and returns (ok, detail).

Template parameters (all optional):
  tags         {key: value spec}; a value spec is
               {"draw": "strata"}  a value of the key's domain, by its
                                   law, from the seed: variant j of n
                                   draws in the j-th of n equal shares
                                   of the law's mass, so that every seed
                                   gets the law's head as often as the
                                   law has it and another part of its
                                   tail, and does about the same work
               {"infix": "roles", ...}   "-" + one of the vocabulary's
                                   roles, drawn as above: a substring
                                   that one service of every team shares
               {"absent": true}    a value in no dictionary
               {"fixed": "500"}    that string
  min_duration_quantile   "0.999": minDuration at that quantile
  window_s     a start/end window of this length, placed by the seed
  limit        default 20
  exhaustive   scan every block (the program's debug tag)
"""

from __future__ import annotations

import base64
import json
import urllib.parse

import numpy as np

from chipbench import reference

EXHAUSTIVE_TAG = "x-dbg-exhaustive"   # search/pipeline.EXHAUSTIVE_SEARCH_TAG


def _value(spec: dict, key: str, manifest: dict, rng, variant: int,
           variants: int) -> str:
    if "fixed" in spec:
        return spec["fixed"]
    if spec.get("absent"):
        return "no-such-value-" + "".join(
            chr(97 + int(c)) for c in rng.integers(0, 26, 6))
    if "infix" in spec:
        vals, p = manifest["vocab"][spec["infix"]], None
    else:
        vals, p = manifest["vocab"]["domains"][key]
    u = (variant + rng.random()) / variants
    cum = (np.arange(1, len(vals) + 1) / len(vals) if p is None
           else np.cumsum(np.asarray(p)))
    rank = min(len(vals) - 1, int(np.searchsorted(cum, u, side="right")))
    return ("-" if "infix" in spec else "") + vals[rank]


def build(params: dict, manifest: dict, rng) -> list[dict]:
    out = []
    variants = int(params.get("variants", 1))
    for variant in range(variants):
        q: dict = {"tags": {}, "limit": int(params.get("limit", 20))}
        for key, spec in sorted((params.get("tags") or {}).items()):
            q["tags"][key] = _value(spec, key, manifest, rng, variant,
                                    variants)
        if params.get("min_duration_quantile"):
            q["min_ms"] = int(manifest["dur_ms_quantile"](
                params["min_duration_quantile"]))
        if params.get("window_s"):
            span = manifest["time_span_s"] - params["window_s"]
            q["start"] = int(manifest["time_base"]
                             + rng.integers(0, max(1, span)))
            q["end"] = q["start"] + int(params["window_s"])
        if params.get("exhaustive"):
            q["exhaustive"] = True
        tags = dict(q["tags"])
        if q.get("exhaustive"):
            tags[EXHAUSTIVE_TAG] = "1"
        http = {"limit": str(q["limit"])}
        if tags:
            http["tags"] = " ".join(f"{k}={v}" for k, v in sorted(tags.items()))
        if q.get("min_ms"):
            http["minDuration"] = f"{q['min_ms']}ms"
        if q.get("start"):
            http["start"], http["end"] = str(q["start"]), str(q["end"])
        out.append({
            "method": "GET",
            "path": "/api/search?" + urllib.parse.urlencode(http),
            "headers": {"X-Scope-OrgID": manifest["tenant"]},
            "ref": q,
        })
    return out


def _expect(request: dict, manifest: dict) -> tuple[dict, list]:
    cache = manifest.setdefault("_search_reference", {})
    key = json.dumps(request["ref"], sort_keys=True)
    if key not in cache:
        cache[key] = (
            reference.answer(request["ref"], manifest,
                             manifest.get("_pool")),
            reference._terms(request["ref"], manifest))
    return cache[key]


def check(request: dict, response: dict, manifest: dict):
    """(ok, detail). Exact comparisons only: limit 0 on every number."""
    if response["status"] != 200:
        return False, f"HTTP {response['status']}"
    try:
        doc = json.loads(base64.b64decode(response["body"]))
    except ValueError as e:
        return False, f"unreadable body: {e}"
    want, terms = _expect(request, manifest)
    q = request["ref"]
    traces = doc.get("traces", [])
    inspected = int(doc.get("metrics", {}).get("inspectedTraces", 0))
    to_entry = manifest["entry_of_trace_id"]
    seen = set()
    for t in traces:
        e = to_entry(t.get("traceId", ""))
        if e is None or e in seen:
            return False, f"trace {t.get('traceId')} unknown or twice"
        seen.add(e)
        if not reference.entry_matches(q, manifest, e[0], e[1], terms):
            return False, f"trace {t.get('traceId')} is not a match"
        start = int(t.get("startTimeUnixNano", 0)) // 1_000_000_000
        if (start != int(manifest["start"][e[0], e[1]])
                or int(t.get("durationMs", 0))
                != int(manifest["dur"][e[0], e[1]])):
            return False, f"trace {t.get('traceId')} start/duration differ"
    if want["deterministic"]:
        if inspected != want["inspected"]:
            return False, (f"inspectedTraces {inspected} != reference "
                           f"{want['inspected']}")
        if want["matches"] <= want["limit"]:
            got = sorted((b << 32) | f for b, f in seen)
            if got != want["keys"].tolist():
                return False, (f"{len(got)} traces, reference has "
                               f"{want['matches']} matches")
        else:
            got = sorted((int(t.get("startTimeUnixNano", 0))
                          // 1_000_000_000 for t in traces), reverse=True)
            if got != want["top_starts"]:
                return False, "not the latest `limit` starts"
    else:
        if len(traces) != want["limit"]:
            return False, (f"{len(traces)} results for limit "
                           f"{want['limit']}, {want['matches']} matches")
        if not 0 < inspected <= want["inspected"]:
            return False, (f"inspectedTraces {inspected} outside "
                           f"(0, {want['inspected']}]")
    return True, ""


def work(request: dict, response: dict) -> dict:
    """What the harness may sum over completed requests."""
    try:
        doc = json.loads(base64.b64decode(response["body"]))
        return {"inspected_entries": int(
            doc.get("metrics", {}).get("inspectedTraces", 0))}
    except ValueError:
        return {}
