"""Served path, client side: 95th percentile of served /api/search
latency, from due time, over every search of the window. A tail below
the knee is queueing at the device: between two sets of six runs it
spread by 8 % and 32 % of its median (PERF.md section 2), too wide to
carry a bound, so it stands here, beside the median that carries one."""
from chipbench.lib import latencies_ms, percentile


def compute(run):
    v = latencies_ms(run, "search")
    return percentile(v, 95) if v else None
