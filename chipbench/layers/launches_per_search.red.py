"""Batcher + coalescer: `launches_per_search.mesh`'s counters on one
chip (`shards="1"`): kernel launches per completed search: a search's
ten groups, fewer under a window (a reducing launch serves one search)."""
from chipbench.layers.sibling import compute_of

compute = compute_of("launches_per_search.mesh")
