"""Batcher + coalescer: dictionaries probed (on the device or the host,
not served from the compile cache) for each completed search:
`tempo_search_dict_probes_total{path}`. A search whose predicate the
batcher's memo or the compile cache knows probes nothing."""
from chipbench.lib import count_ok, delta

NAME = "tempo_search_dict_probes_total"


def compute(run):
    if NAME not in run["counters"]["after"]:
        return None
    n = count_ok(run, "search")
    probes = delta(run, NAME, path="device") + delta(run, NAME, path="host")
    return probes / n if n else None
