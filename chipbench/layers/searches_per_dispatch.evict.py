"""Batcher + coalescer: `searches_per_dispatch.scan` in the cell whose
groups are evicted: sixteen callers over windows of their own meet in
fewer groups than sixteen tenant-wide scans do, and a caller that waits
for a put is not there to be fused with."""
from chipbench.layers.sibling import compute_of

compute = compute_of("searches_per_dispatch.scan")
