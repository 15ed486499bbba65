"""Op `search_highcard`: GET /api/search for a customer's traces by an id
typed whole, typed in part, or pasted in part: what support and on-call
engineers send against a tag of a million values.

Everything but the needle is op `search`'s: the other template
parameters (`tags`, `min_duration_quantile`, `limit`, `variants`; see
chipbench/ops/search.py), the request it builds, the comparison with the
plain reference and what the harness may sum. The needle for
`customer.id` is made here, by `needle`:

  exact      a whole id
  prefix     `cus_` and the id's first `letters` letters: 1 / 26**letters
             of the tenant's ids, one run of every sorted dictionary
  fragment   `letters` letters from the middle of the id: the ids that
             hold them lie scattered over a sorted dictionary
  absent     a well-formed id of a rank past the tenant's: no block holds
             it, the probe prunes every block and nothing is scanned

The id is drawn like op `search`'s values: variant j of n in the j-th of
n equal shares of the mass of the domain's law, so every seed asks for
heavy customers as often as the law has them. A fragment that `cus_`
itself holds (`cu`, `us`) would hit every id, and is drawn again.

`check` adds the configuration's third guarantee to op `search`'s: no
answer while the program has probed a dictionary on the host
(`tempo_search_dict_probes_total{path="host"}` is 0 from the start of
the process): at this cardinality the shipped path is the device's.

The reference's answers are computed for every predicate of the pool at
once, on threads, at the first `check` (`_expect_all`): one answer walks
a table of 1.25M values and 256 x 1.25M presence flags in numpy, 0.8 s,
and one after the other forty of them took 45 s after a window on a
v5e's host (my chip run, PR 33). The functions and their inputs are op
`search`'s own (`search._expect`, which keeps each answer under its
request's `ref`); only who waits for whom changes.

`build` refuses a program whose `/metrics` lacks
`tempo_search_scan_membership_total`, as the generator of this cell's
corpus does before it (chipbench/generators/otel_highcard.py says why).

The op's name starts with `search`, which `lib.count_ok` and
`lib.latencies_ms` match on.
"""

from __future__ import annotations

import numpy as np

from chipbench.generators.otel_highcard import (
    ID_LETTERS, customer_ids, require_membership,
)
from chipbench.ops import search
from chipbench.ops.search import work  # noqa: F401

KEY = "customer.id"
HOST_PROBES = "tempo_search_dict_probes_total"


def _needle(kind: str, letters: int, manifest: dict, rng, variant: int,
            variants: int) -> str:
    if kind == "absent":
        rank = manifest["customers"] + int(rng.integers(0, 1 << 20))
        return customer_ids(rank, rank + 1)[0]
    ids, p = manifest["vocab"]["domains"][KEY]
    u = (variant + rng.random()) / variants
    rank = int(np.searchsorted(np.cumsum(np.asarray(p)), u, side="right"))
    one = ids[min(len(ids) - 1, rank)]
    if kind == "exact":
        return one
    if kind == "prefix":
        return one[:4 + letters]
    while True:
        at = 5 + int(rng.integers(0, ID_LETTERS - letters - 1))
        if one[at:at + letters] not in "cus_":
            return one[at:at + letters]


def build(params: dict, manifest: dict, rng) -> list[dict]:
    require_membership("op search_highcard")
    spec = params["needle"]
    variants = int(params.get("variants", 1))
    out = []
    for variant in range(variants):
        needle = _needle(spec["kind"], int(spec.get("letters", 0)),
                         manifest, rng, variant, variants)
        out.extend(search.build(
            dict({k: v for k, v in params.items() if k != "needle"},
                 variants=1,
                 tags=dict(params.get("tags") or {},
                           **{KEY: {"fixed": needle}})),
            manifest, rng))
    manifest.setdefault("_highcard_pool", []).extend(out)
    return out


def _expect_all(manifest: dict) -> None:
    """The reference's answer to every request `build` made, several at
    a time (each still spreads its blocks over the harness's pool)."""
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(8) as several:
        list(several.map(lambda r: search._expect(r, manifest),
                         manifest.pop("_highcard_pool", [])))


def check(request: dict, response: dict, manifest: dict):
    from tempo_tpu.observability.metrics import REGISTRY

    from chipbench.lib import metric_sum, parse_metrics

    if manifest.get("_highcard_pool"):
        _expect_all(manifest)
    ok, why = search.check(request, response, manifest)
    if not ok:
        return ok, why
    now = manifest.get("_host_probes")
    if now is None:
        # once a run: the comparison comes after the window
        now = manifest["_host_probes"] = metric_sum(
            parse_metrics(REGISTRY.expose()), HOST_PROBES, path="host")
    if now:
        return False, (f"{now:.0f} host dictionary probes since the "
                       "process started (limit 0)")
    return True, ""
