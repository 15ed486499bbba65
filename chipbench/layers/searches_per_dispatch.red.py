"""Batcher + coalescer: `searches_per_dispatch.scan` in the cell of RED
dashboards: group scans served per kernel launch. A fused launch sorts
one key column a member all the same, and slower than solo launches do
(`scripts/red_bench.py`), so since PR 48 the coalescer launches `?agg=`
members solo and this reads 1.0; on PR 48's parent, which fused them,
1.1. What a cheaper fused reduction would raise."""
from chipbench.layers.sibling import compute_of

compute = compute_of("searches_per_dispatch.scan")
