"""Batcher + coalescer: groups a completed search took from the staged
cache, resident or not: `tempo_search_batch_cache_events_total`
(hit + miss) over the searches completed. Groups that the header prune
skipped whole are not taken. With groups cut in id order every window
touches every group; cut in time, the groups its hours lie in."""
from chipbench.lib import count_ok, delta

NAME = "tempo_search_batch_cache_events_total"


def compute(run):
    n = count_ok(run, "search")
    visits = delta(run, NAME, result="hit") + delta(run, NAME, result="miss")
    return visits / n if n and visits else None
