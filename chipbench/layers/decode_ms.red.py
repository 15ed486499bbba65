"""D2H + merge: host time a completed search spends turning dense counts
into its answer's series: summed `analytics.decode` spans (a member's
`[K]` counts decoded into series and added to the search's aggregate,
once a group) and `results.merge_agg` spans (a sub-response's aggregate
parsed from JSON and merged, at the querier's and the frontend's
fan-in), over the searches completed in the window. A program without
the spans gives nothing to read."""
from chipbench.lib import count_ok
from chipbench.layers.spans import ms

NAMES = ("analytics.decode", "results.merge_agg")


def compute(run):
    spans = [s for s in run["spans"] if s["name"] in NAMES]
    n = count_ok(run, "search")
    return sum(ms(s) for s in spans) / n if spans and n else None
