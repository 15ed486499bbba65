"""Kernel: distinct jit keys the scan program's launches have shown
since the process started, at the window's end
(`tempo_search_scan_jit_keys`): page bucket x block-axis bucket x
(Q, T, R). 35 groups of 20 block counts in five buckets: ~40, where a key
a count would be up to 160. A program without the gauge gives nothing
to read."""
from chipbench.lib import metric_sum


def compute(run):
    n = metric_sum(run["counters"]["after"], "tempo_search_scan_jit_keys")
    return n or None
