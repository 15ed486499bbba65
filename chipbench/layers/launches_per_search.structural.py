"""Batcher + coalescer: `launches_per_search.mesh`'s counters on one
chip (`shards="1"`): kernel launches per completed search: the groups of
the tenant, since a structural launch is its own (no stacking gate is on,
so none fuses)."""
from chipbench.layers.sibling import compute_of

compute = compute_of("launches_per_search.mesh")
