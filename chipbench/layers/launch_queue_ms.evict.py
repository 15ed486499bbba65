"""Batcher + coalescer: `launch_queue_ms.scan` in the cell whose groups
are evicted: how far the host ran ahead of the device, host-observed
(the watcher's `device.scan` against `coalescer.launch`)."""
from chipbench.layers.sibling import compute_of

compute = compute_of("launch_queue_ms.scan")
