"""Batcher + coalescer: `launch_cpu_ms` in the cell of structural
searches: CPU milliseconds a launch costs the host, against the hundreds
of milliseconds it keeps the device."""
from chipbench.layers.sibling import compute_of

compute = compute_of("launch_cpu_ms")
