"""Batcher + coalescer: group scans served per kernel launch: a
search's scan of one group is one submit, a fused launch serves several.
Queries through fused launches / launches, from the counters."""
from chipbench.lib import delta


def compute(run):
    solo = delta(run, "tempo_search_scan_dispatches_total", mode="batched")
    fused = delta(run, "tempo_search_scan_dispatches_total", mode="coalesced")
    q = delta(run, "tempo_search_coalesced_queries_total")
    return (solo + q) / (solo + fused) if solo + fused else None
