"""Batcher + coalescer: misses among the per-group lookups of the
prepare memo over the window, from
`tempo_search_prepare_memo_total{result}`, counted at the lookup."""
from chipbench.lib import delta

NAME = "tempo_search_prepare_memo_total"


def compute(run):
    miss = delta(run, NAME, result="miss")
    n = miss + delta(run, NAME, result="hit")
    return 100.0 * miss / n if n else None
