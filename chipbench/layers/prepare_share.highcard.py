"""Batcher + coalescer: what of a search's time in the batcher is
`batcher.prepare` (a group's predicate compile on a memo miss, the
dictionary probes inside it): summed `batcher.prepare` over summed
`batcher.Search`, from the spans."""
from chipbench.lib import durations_ms


def compute(run):
    whole = sum(durations_ms(run["spans"], {"batcher.Search"}))
    return (100.0 * sum(durations_ms(run["spans"], {"batcher.prepare"}))
            / whole if whole else None)
