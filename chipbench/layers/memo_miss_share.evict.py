"""Batcher + coalescer: `memo_miss_share` in the cell whose groups are
evicted: what of the prepare memo dies with an evicted group. A program
that keeps the memo on the staged batch alone pays `prepare` again for
every predicate at every re-stage (39 % of lookups on a v5e, PR 30); one
that hands it to the host-tier entry reads 0 here."""
from chipbench.layers.memo_miss_share import compute  # noqa: F401
