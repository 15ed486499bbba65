"""Staging: group visits that found their group gone from HBM and
staged it again, among all group visits of the window:
`tempo_search_batch_cache_events_total{result}`, miss / (hit + miss).
A resident tenant reads 0; a tenant larger than
`search_batch_cache_bytes` pays this share of its visits in H2D."""
from chipbench.lib import delta

NAME = "tempo_search_batch_cache_events_total"


def compute(run):
    miss = delta(run, NAME, result="miss")
    n = miss + delta(run, NAME, result="hit")
    return 100.0 * miss / n if n else None
