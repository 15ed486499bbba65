"""Query frontend: median self time of `frontend.Search` (sharding the
blocklist into jobs, queueing, merging sub-responses)."""
from chipbench.lib import median, self_times_ms


def compute(run):
    return median(self_times_ms(run["spans"], {"frontend.Search"}))
