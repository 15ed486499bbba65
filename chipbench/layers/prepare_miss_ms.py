"""Batcher + coalescer: median duration of `batcher.prepare`: one
group's per-block predicate compile on a memo miss, Python under the
GIL."""
from chipbench.lib import median
from chipbench.layers.spans import ms, named


def compute(run):
    return median([ms(s) for s in named(run["spans"], "batcher.prepare")])
