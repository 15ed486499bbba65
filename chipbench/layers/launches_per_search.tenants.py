"""Batcher + coalescer: `launches_per_search.mesh`'s counters on one
chip (`shards="1"`): kernel launches per completed search, the groups of
the tenant asked when nothing fuses: 1.7 by the law of the tenants (the
heaviest has three groups, most have one)."""
from chipbench.layers.sibling import compute_of

compute = compute_of("launches_per_search.mesh")
