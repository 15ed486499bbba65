"""Batcher + coalescer: `memo_miss_share` in the cell of
high-cardinality tags: 40 predicates a seed against a memo of 32 a
group, so a miss pays `prepare` (and, past the compile cache, a probe of
every dictionary of the group) inside the window."""
from chipbench.layers.memo_miss_share import compute  # noqa: F401
