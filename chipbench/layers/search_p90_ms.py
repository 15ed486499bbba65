"""Served path, client side: 90th percentile of served /api/search
latency, from due time, over every search of the window: recorded so
that the ledger shows whether a tail nearer the median than the 95th
is steady enough to carry a bound."""
from chipbench.lib import latencies_ms, percentile


def compute(run):
    v = latencies_ms(run, "search")
    return percentile(v, 90) if v else None
