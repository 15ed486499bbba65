"""Batcher + coalescer, on a mesh: collective kernel launches per
completed search: `tempo_search_scan_dispatches_total` in the modes
`batched` and `coalesced` with `shards` the configuration's chip count,
over the searches that completed in the window. The groups staged when
nothing fuses and nothing is pruned; fusing lowers it. A program whose
counter has no `shards` label gives nothing to read."""
from chipbench.lib import count_ok, delta

MODES = ("batched", "coalesced")


def compute(run):
    shards = run["config"].get("chips", {}).get("count")
    n = count_ok(run, "search")
    d = sum(delta(run, "tempo_search_scan_dispatches_total", mode=m,
                  shards=shards) for m in MODES)
    return d / n if n and d and shards else None
