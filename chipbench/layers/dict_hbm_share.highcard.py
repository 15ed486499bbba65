"""Staging: what of the staged-batch cache's HBM is packed dictionaries
for the device probe (`tempo_search_probe_dict_bytes` over
`tempo_search_hbm_cache_bytes`, as the window ends): 5 B in HBM for
each byte of a dictionary, padded to a power of two."""
from chipbench.lib import metric_sum


def compute(run):
    after = run["counters"]["after"]
    whole = metric_sum(after, "tempo_search_hbm_cache_bytes")
    return (100.0 * metric_sum(after, "tempo_search_probe_dict_bytes")
            / whole if whole else None)
