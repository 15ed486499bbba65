"""Staging: mean duration of the `batcher.stage` spans whose `cache` is
`hbm_miss_host_hit`: what a search waited for a group that HBM had let
go and the host tier still held (the put, or the rest of a look-ahead's
put). A run in which nothing was re-staged has no such span."""
from chipbench.layers.spans import ms, named


def compute(run):
    v = [ms(s) for s in named(run["spans"], "batcher.stage")
         if s["attributes"].get("cache") == "hbm_miss_host_hit"]
    return sum(v) / len(v) if v else None
