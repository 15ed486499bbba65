"""Batcher + coalescer: median duration of `batcher.Search` (group
loop, staging lookups, prepare, dispatch, drain)."""
from chipbench.lib import durations_ms, median


def compute(run):
    return median(durations_ms(run["spans"], {"batcher.Search"}))
