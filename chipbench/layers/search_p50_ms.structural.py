"""Served path, client side: the median served `/api/search` latency in
the cell of structural searches, from due time, over every search of
the window: sixteen callers behind one device, so about sixteen mean
launches. What the cell's users feel; no end-to-end metric, since in a
closed loop past the device's capacity it is the queue's length."""
from chipbench.lib import latencies_ms, percentile


def compute(run):
    v = latencies_ms(run, "search")
    return percentile(v, 50) if v else None
