"""Median served /api/search latency, client side, from when each
request was due; every search of the window counts."""
from chipbench.lib import latencies_ms, percentile


def compute(run):
    v = latencies_ms(run, "search")
    return percentile(v, 50) if v else None
