"""Batcher + coalescer: median duration of `coalescer.wait`: a query
submitted to the coalescer -> its launch enqueued on the device (the
window, the stacking and the kernel call)."""
from chipbench.lib import median
from chipbench.layers.spans import ms, named


def compute(run):
    return median([ms(s) for s in named(run["spans"], "coalescer.wait")])
