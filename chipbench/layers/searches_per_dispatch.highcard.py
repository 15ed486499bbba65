"""Batcher + coalescer: `searches_per_dispatch.scan` in the cell of
high-cardinality tags: members that bring a hit mask fuse among
themselves, apart from those that bring ranges."""
from chipbench.layers.sibling import compute_of

compute = compute_of("searches_per_dispatch.scan")
