"""Batcher + coalescer: share of the querier's search jobs that spent no
time in `prepare` at all, because every group they touched had the
request's predicate in its memo (`batcher._QUERY_CACHE_MAX` predicates a
group: header prune, per-block compile tables). From the lowest bucket
of `tempo_search_query_stage_seconds{stage="prepare"}` over its count.
The mix sends more distinct predicates than the memo holds, so this is
below 100 %; a miss pays `prepare_ms`."""
from chipbench.lib import delta

NAME = "tempo_search_query_stage_seconds"


def compute(run):
    n = delta(run, NAME + "_count", stage="prepare")
    hit = delta(run, NAME + "_bucket", stage="prepare", le="0.0001")
    return 100.0 * hit / n if n else None
