"""Staging: what of the staged-batch cache's HBM is span columns
(`tempo_search_structural_span_bytes` over
`tempo_search_hbm_cache_bytes`, as the window ends): 17 B a span row and
8 B a kv slot, pad rows included, beside 61 B an entry. A program
without the gauge gives nothing to read."""
from chipbench.lib import metric_sum


def compute(run):
    after = run["counters"]["after"]
    whole = metric_sum(after, "tempo_search_hbm_cache_bytes")
    spans = metric_sum(after, "tempo_search_structural_span_bytes")
    return 100.0 * spans / whole if whole and spans else None
