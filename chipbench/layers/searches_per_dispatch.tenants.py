"""Batcher + coalescer: `searches_per_dispatch.scan` in the cell of many
tenants: sixteen callers on 32 tenants meet on one staged group far less
often than on one tenant's ten."""
from chipbench.layers.sibling import compute_of

compute = compute_of("searches_per_dispatch.scan")
