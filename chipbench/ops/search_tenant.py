"""Op `search_tenant`: GET /api/search by one of many tenants: what the
teams of a shared cluster send, each over its own traces, all at once.

Everything but the tenant is op `search`'s: the template parameters
(`tags`, `min_duration_quantile`, `limit`, `exhaustive`, `variants`; see
chipbench/ops/search.py), the request it builds and what the harness may
sum (`work`: `inspected_entries`). The tenant goes into `X-Scope-OrgID`
and is drawn here:

  tenant_class   the class of tenants this template entry draws from: a
                 tenant's class is the power of two above the block
                 count of its last group (the manifest's `tenant_class`,
                 chipbench/generators/otel_tenants.py), the shape its
                 smallest launches have. The mix has one entry a class
                 and template, so that set-up's bursts of one entry's
                 pool meet on tenants of one class. A class no tenant
                 is in builds no request.

Within its class a request's tenant is drawn by the tenants' law
(Zipf over their ranks, the law of their sizes: `tenant_law`), one draw
in each of `variants` equal shares of the class's mass, so every seed
asks the heavy tenants as often as the law has them; which variant gets
which share is shuffled by the seed, or the most popular service would
always be asked of the heaviest tenant. The pool is ordered
tenant-major, heaviest first: a burst of the pool's first requests then
lands on one tenant and its launches fuse.

`check` holds an answer to the plain reference computed over THAT
TENANT'S arrays alone (`otel_tenants.view`): exact `inspectedTraces`
and match set, and every trace id an entry of the tenant's own blocks;
the ids carry the corpus-wide block index, so a trace of another
tenant's block, or of a pad row, maps to nothing and the answer fails.

The op's name starts with `search`, which `lib.count_ok` and
`lib.latencies_ms` match on.

The cell this op drives reads the scan program's jit keys and pad rows
from `tempo_search_scan_jit_keys` and
`tempo_search_launch_table_rows_total`. A program without them is driven
all the same, and the readers of the two return nothing there: the one
such program (PR 40's parent, a jit key for every block count of a
group) was run under this traffic on a v5e and ended inside its limits
(a first run of 378 s with 57 keys, 32 of them compiled cold in 191 s;
a warm one of 272 s with 63 keys; 3 and 1 jit misses inside the
windows; `correct: true`), so it is the cell's baseline and nothing
here refuses it.
"""

from __future__ import annotations

import numpy as np

from chipbench.generators.otel_tenants import view
from chipbench.ops import search
from chipbench.ops.search import work  # noqa: F401


def build(params: dict, manifest: dict, rng) -> list[dict]:
    want = int(params["tenant_class"])
    ranks = [r for r, t in enumerate(manifest["tenants"])
             if manifest["tenant_class"][t] == want]
    if not ranks:
        return []
    out = search.build({k: v for k, v in params.items()
                        if k != "tenant_class"}, manifest, rng)
    law = np.asarray(manifest["tenant_law"])[ranks]
    cum = np.cumsum(law / law.sum())
    for r, share in zip(out, rng.permutation(len(out))):
        u = (int(share) + rng.random()) / len(out)
        k = min(len(ranks) - 1, int(np.searchsorted(cum, u, side="right")))
        r["tenant_rank"] = ranks[k]
        r["headers"] = {"X-Scope-OrgID": manifest["tenants"][ranks[k]]}
    out.sort(key=lambda r: r["tenant_rank"])
    return out


def check(request: dict, response: dict, manifest: dict):
    v = view(manifest, request["headers"]["X-Scope-OrgID"])
    v["_pool"] = manifest.get("_pool")
    return search.check(request, response, v)
