"""Query frontend: per search the longest `frontend.queue_wait` (a
sub-request enqueued in the per-tenant fair queue -> a worker starts
it), p95 over searches."""
from chipbench.layers.spans import ms, named, p95, searches


def compute(run):
    longest = []
    for spans in searches(run["spans"]).values():
        waits = [ms(w) for w in named(spans, "frontend.queue_wait")]
        if waits:
            longest.append(max(waits))
    return p95(longest)
