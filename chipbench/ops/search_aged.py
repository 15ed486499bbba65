"""Op `search_aged`: GET /api/search over a time window placed by its
age: what a hunt through a tenant's recent hours sends (Grafana always
sends `start`/`end`).

Everything but the window is op `search`'s: the template parameters
(`tags`, `min_duration_quantile`, `limit`, `variants`; see
chipbench/ops/search.py), the comparison with the plain reference
(`check`) and what the harness may sum (`work`) are imported, not
restated. `window_s` means something else here:

  window_s   the window's length. It ends `age` whole hours before the
             corpus's newest time (`time_base + time_span_s`) and starts
             `window_s` earlier, so it lies inside the corpus. `age` is
             one of the positions the length leaves in the corpus's
             span, counted in hours (24 h of data: 24 positions for
             1 h, 19 for 6 h, one for the day), drawn by Zipf(1.1),
             rank 1 the newest (`AGE_ZIPF_S`). Like a tag value it is
             drawn by strata: one draw in each of `variants` equal
             shares of the law's mass, so every seed looks at the
             newest hours as often as the law has them and at another
             part of the older ones. Which variant gets which share is
             shuffled by the seed, or the most popular service would
             always have the newest window.

The op's name starts with `search`, which `lib.count_ok` and
`lib.latencies_ms` match on.

The cell this op drives states a guarantee in one number of the
program's `/metrics`, `tempo_search_hbm_cache_peak_bytes` (`PEAK`): how
far the staged-batch cache ever stood over its budget. A program whose
`/metrics` has no such line cannot be held to the guarantee, and the op
does not drive it: `build` exits before the first request, with a line
that says so. It asks `/metrics`, the surface the cell's readers read,
for the one name the guarantee is stated in, and nothing else of the
program. What the refusal spares: the one program without the line
(PR 30's parent) was run on a v5e under this traffic. Every search
pinned every group it took until it ended, a group staged while all
others were pinned was evicted by its own insert and scanned as a copy
the budget no longer counted, and in the bursts of set-up the chip's
16 GB filled (RESOURCE_EXHAUSTED), the breaker opened, the host route
copied the tenant once more and the machine killed the process at its
40 GiB, 268 s in. A benchmark that tries a new cell on the parent first
needs a result or a refusal from it, and a kill is neither.
"""

from __future__ import annotations

import numpy as np

from chipbench.ops import search
from chipbench.ops.search import check, work  # noqa: F401

AGE_ZIPF_S = 1.1
HOUR_S = 3600
PEAK = "tempo_search_hbm_cache_peak_bytes"


def age_positions(span_s: int, window_s: int) -> int:
    """Whole-hour ages at which a window of `window_s` lies inside a
    corpus of `span_s`."""
    return max(1, (int(span_s) - int(window_s)) // HOUR_S + 1)


def draw_age(positions: int, share: int, shares: int, rng) -> int:
    """An age in hours (0 = the newest) from the `share`-th of `shares`
    equal parts of the mass of Zipf(AGE_ZIPF_S) over `positions`."""
    w = 1.0 / np.arange(1, positions + 1, dtype=np.float64) ** AGE_ZIPF_S
    cum = np.cumsum(w / w.sum())
    u = (share + rng.random()) / shares
    return min(positions - 1, int(np.searchsorted(cum, u, side="right")))


def publishes(name: str) -> bool:
    """Whether the program's `/metrics` has a metric of that name (its
    `# TYPE` line is there from the start, whatever its value)."""
    from tempo_tpu.observability.metrics import REGISTRY

    return f"# TYPE {name} " in REGISTRY.expose()


def build(params: dict, manifest: dict, rng) -> list[dict]:
    if not publishes(PEAK):
        raise SystemExit(
            f"op search_aged: this program's /metrics has no {PEAK}, the "
            "number its cell's budget guarantee is stated in; not run "
            "(chipbench/ops/search_aged.py says what happened when one was)")
    window_s = int(params["window_s"])
    out = search.build({k: v for k, v in params.items() if k != "window_s"},
                       manifest, rng)
    newest = int(manifest["time_base"]) + int(manifest["time_span_s"])
    positions = age_positions(manifest["time_span_s"], window_s)
    shares = rng.permutation(len(out))
    for r, share in zip(out, shares):
        age = draw_age(positions, int(share), len(out), rng)
        end = newest - age * HOUR_S
        r["ref"].update(start=end - window_s, end=end)
        r["path"] += f"&start={end - window_s}&end={end}"
    return out
