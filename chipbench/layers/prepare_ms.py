"""Batcher + coalescer: mean `prepare` time of a querier's search job
(header prune and query compile against every block of each group whose
memo does not have the predicate), from
`tempo_search_query_stage_seconds{stage="prepare"}`."""
from chipbench.lib import delta

NAME = "tempo_search_query_stage_seconds"


def compute(run):
    n = delta(run, NAME + "_count", stage="prepare")
    s = delta(run, NAME + "_sum", stage="prepare")
    return s / n * 1e3 if n else None
