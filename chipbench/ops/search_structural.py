"""Op `search_structural`: GET /api/search?q=<json>: a search that asks
WHERE in the call tree something happened (errors below a service, a
direct dependency, an N+1 fan-out to a store, a service's slow p90, slow
client calls that were no plain success): what an on-call engineer or an
alert rule sends a tracing backend, each over the whole tenant. The JSON
is the program's structural query form
(docs/search-structural-queries.md), which stands where upstream Tempo
later put TraceQL's structural operators (`{ } >> { }`, `{ } > { }`,
`| count() > n`, duration quantiles).

`build(params, manifest, rng) -> requests` draws `variants` concrete
requests of one template; `check` holds one answer to the plain
reference (`chipbench/reference_structural.py`), exactly; `work` is op
`search`'s (`inspected_entries`).

Template parameters:
  q           the query with `$NAME` where a drawn value goes: a
              template is one shape of plan, its variants differ in the
              values of the plan's tables only
  draw        {NAME: spec}, a spec one of
              {"service": "strata"}     a service that spans carry (the
                  manifest's `span_services`), by the trace-level law of
                  `service.name` over them: variant j of n draws in the
                  j-th of n equal shares of the law's mass, as op
                  `search` draws its values
              {"domain": key}           a value of that trace-level
                  domain, by its law, by strata; with "uniform": true
                  by equal shares (8 variants over 8 values: each once)
              {"edge": "parent" | "child", "non_edges": k}   the two
                  ends of a call edge of the application's graph
                  (`call_edges`) in one team, the team by strata over the
                  teams' mass; the last k variants take a pair of roles
                  that is NO edge (nothing matches anywhere)
              {"dur_quantile": "0.99"}  milliseconds at that quantile of
                  the trace durations' law; a list gives variant j its
                  j-th quantile
              {"count_quantile": "0.9999", "of": <span>}   the count of
                  spans matching `of` (drawn values filled in) a trace,
                  at that quantile over the traces of the corpus's first
                  8 blocks, by the plain reference
  limit       default 20
  exhaustive  scan every block to the end (the program's debug tag), so
              the answer is a function of the data whatever matches:
              exact `inspectedTraces`, and the whole match set or, past
              `limit` matches, the `limit` latest starts

The op's name starts with `search`, which `lib.count_ok` and
`lib.latencies_ms` match on.

The cell this op drives reads counters and spans that a program before
PR 44 lacks (`tempo_search_structural_*`, `structural.compile`,
`batcher.stage_spans`), and that program cannot end the cell inside the
time a run may take: `build` exits 1 on it before the first request, as
the generator does before the first block
(`otel_calltree.require_span_counters`, which says what happened when
it was driven).
"""

from __future__ import annotations

import base64
import json
import urllib.parse

import numpy as np

from chipbench import reference_structural as rs
from chipbench.generators.otel_calltree import require_span_counters
from chipbench.ops.search import EXHAUSTIVE_TAG, work  # noqa: F401

QUANTILE_BLOCKS = 8


def _strata(p, variant: int, variants: int, rng) -> int:
    u = (variant + rng.random()) / variants
    cum = np.cumsum(np.asarray(p, dtype=np.float64))
    return min(len(cum) - 1,
               int(np.searchsorted(cum / cum[-1], u, side="right")))


def _fill(node, env: dict):
    if isinstance(node, str) and node.startswith("$"):
        return env[node[1:]]
    if isinstance(node, dict):
        return {k: _fill(v, env) for k, v in node.items()}
    if isinstance(node, list):
        return [_fill(v, env) for v in node]
    return node


def _law(manifest: dict, key: str) -> dict:
    vals, p = manifest["vocab"]["domains"][key]
    return dict(zip(vals, p))


def _draw(draws: dict, manifest: dict, rng, variant: int,
          variants: int) -> dict:
    env: dict = {}
    later = []
    for name, spec in sorted(draws.items()):
        if "service" in spec:
            law = _law(manifest, "service.name")
            svcs = manifest["span_services"]
            env[name] = svcs[_strata([law[s] for s in svcs], variant,
                                     variants, rng)]
        elif "domain" in spec:
            vals, p = manifest["vocab"]["domains"][spec["domain"]]
            if spec.get("uniform"):
                p = [1.0] * len(vals)
            env[name] = vals[_strata(p, variant, variants, rng)]
        elif "edge" in spec:
            if "_edge" not in env:
                law = _law(manifest, "service.name")
                teams, roles = (manifest["vocab"]["teams"],
                                manifest["vocab"]["roles"])
                mass = [sum(law[f"{t}-{r}"] for r in roles) for t in teams]
                team = teams[_strata(mass, variant, variants, rng)]
                edges = [tuple(e) for e in manifest["call_edges"]]
                if variant >= variants - int(spec.get("non_edges", 0)):
                    used = sorted({r for e in edges for r in e})
                    none = [(a, b) for a in used for b in used
                            if a != b and (a, b) not in edges]
                    a, b = none[int(rng.integers(0, len(none)))]
                else:
                    a, b = edges[int(rng.integers(0, len(edges)))]
                env["_edge"] = {"parent": f"{team}-{a}",
                                "child": f"{team}-{b}"}
            env[name] = env["_edge"][spec["edge"]]
        elif "dur_quantile" in spec:
            qs = spec["dur_quantile"]
            env[name] = int(manifest["dur_ms_quantile"](
                qs[variant % len(qs)] if isinstance(qs, list) else qs))
        else:
            later.append((name, spec))
    for name, spec in later:          # these read the values drawn above
        of = _fill(spec["of"], env)
        counts = np.concatenate([
            rs.span_counts(of, manifest, rs.block_of(manifest, b))
            for b in range(min(QUANTILE_BLOCKS,
                               len(manifest["span_count"])))])
        env[name] = int(np.quantile(counts, float(spec["count_quantile"]),
                                    method="higher"))
    return env


def build(params: dict, manifest: dict, rng) -> list[dict]:
    require_span_counters("op search_structural")
    out = []
    variants = int(params.get("variants", 1))
    for variant in range(variants):
        env = _draw(params.get("draw") or {}, manifest, rng, variant,
                    variants)
        ref = {"q": _fill(params["q"], env),
               "limit": int(params.get("limit", 20))}
        http = {"q": json.dumps(ref["q"], separators=(",", ":")),
                "limit": str(ref["limit"])}
        if params.get("exhaustive"):
            ref["exhaustive"] = True
            http["tags"] = f"{EXHAUSTIVE_TAG}=1"
        out.append({
            "method": "GET",
            "path": "/api/search?" + urllib.parse.urlencode(http),
            "headers": {"X-Scope-OrgID": manifest["tenant"]},
            "ref": ref,
        })
    return out


def _expect(request: dict, manifest: dict) -> dict:
    cache = manifest.setdefault("_structural_reference", {})
    key = json.dumps(request["ref"], sort_keys=True)
    if key not in cache:
        cache[key] = rs.answer(request["ref"], manifest,
                               manifest.get("_pool"))
    return cache[key]


def check(request: dict, response: dict, manifest: dict):
    """(ok, detail). Exact comparisons only: limit 0 on every number."""
    if response["status"] != 200:
        return False, f"HTTP {response['status']}"
    try:
        doc = json.loads(base64.b64decode(response["body"]))
    except ValueError as e:
        return False, f"unreadable body: {e}"
    want = _expect(request, manifest)
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
        if not rs.entry_matches(q, manifest, e[0], e[1]):
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
