"""Querier / TempoDB: self time of the querier's job span and of
`tempodb.Search` (job planning, header cache), summed per search,
median over searches."""
from chipbench.lib import median, self_times_ms

NAMES = {"querier.SearchBlocks", "querier.SearchBlock", "tempodb.Search",
         "querier.SearchRecent"}


def compute(run):
    spans = [s for s in run["spans"] if s["name"] in NAMES]
    per_trace: dict = {}
    for s, ms in zip(spans, self_times_ms(run["spans"], NAMES)):
        per_trace[s["trace_id"]] = per_trace.get(s["trace_id"], 0.0) + ms
    return median(list(per_trace.values()))
