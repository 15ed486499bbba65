"""Batcher + coalescer: kernel launches on the device per completed
search (counter deltas over the window)."""
from chipbench.lib import count_ok, delta

MODES = ("batched", "coalesced")


def compute(run):
    n = count_ok(run, "search")
    d = sum(delta(run, "tempo_search_scan_dispatches_total", mode=m)
            for m in MODES)
    return d / n if n and d else None
