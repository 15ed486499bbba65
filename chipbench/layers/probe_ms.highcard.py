"""Batcher + coalescer: one device probe of one dictionary, fenced: the
`execute` stage of the `dict_probe` dispatches over their count
(`tempo_search_dispatch_stage_seconds{mode="dict_probe"}`), since the
process started: set-up sends every predicate once and the compile
cache keeps the products (128 a dictionary where they are ranges), so
that is where the probes are; a window probes only what the cache let
go. A program that never probed on the device gives None."""
from chipbench.lib import metric_sum

NAME = "tempo_search_dispatch_stage_seconds"


def compute(run):
    after = run["counters"]["after"]
    s = metric_sum(after, NAME + "_sum", stage="execute", mode="dict_probe")
    n = metric_sum(after, NAME + "_count", stage="execute", mode="dict_probe")
    return s / n * 1e3 if n else None
