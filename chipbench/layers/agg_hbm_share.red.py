"""Staging: what of the staged-batch cache's HBM is `?agg=` key columns
(`tempo_search_agg_staged_bytes` over `tempo_search_hbm_cache_bytes`, as
the window ends): 4 B a staged entry, pad pages included, beside 61 B of
columns. A program without the gauge (its key columns were outside the
budget) gives nothing to read."""
from chipbench.lib import metric_sum


def compute(run):
    after = run["counters"]["after"]
    whole = metric_sum(after, "tempo_search_hbm_cache_bytes")
    keys = metric_sum(after, "tempo_search_agg_staged_bytes")
    return 100.0 * keys / whole if whole and keys else None
