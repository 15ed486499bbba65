"""Batcher + coalescer: `coalesce_wait_ms` in the cells judged on
`scan_rate`: median duration of `coalescer.wait`."""
from chipbench.layers.coalesce_wait_ms import compute  # noqa: F401
