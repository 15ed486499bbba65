"""Staging: the most HBM device hit masks ever pinned since the process
started (`tempo_search_probe_mask_peak_bytes`: the compile cache's
products and the prepare memo's stacks), over
`storage.search_batch_cache_bytes` (`hbm_peak_over_budget.evict`'s
`budget`). 0 where no needle ever left the probe as a mask; a program
without the gauge gives None."""
from chipbench.layers.sibling import compute_of
from chipbench.lib import metric_sum

NAME = "tempo_search_probe_mask_peak_bytes"
budget = compute_of("hbm_peak_over_budget.evict").__globals__["budget"]


def compute(run):
    after = run["counters"]["after"]
    b = budget(run)
    return metric_sum(after, NAME) / b if NAME in after and b else None
