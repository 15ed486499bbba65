"""Batcher + coalescer: `coalesce_wait_ms` in the cell whose groups are
evicted: median duration of `coalescer.wait`."""
from chipbench.layers.coalesce_wait_ms import compute  # noqa: F401
