"""Batcher + coalescer: p95 duration of `batcher.Search`; `batcher_ms`
is its median."""
from chipbench.layers.spans import ms, named, p95


def compute(run):
    return p95([ms(s) for s in named(run["spans"], "batcher.Search")])
