"""Kernel: `scan_kernel_roofline` in the cell whose groups are evicted
(device trace, `costs.scan_bytes` of one group a launch, 819 GB/s;
memory bound): no new kernel, the same program at 24 groups of 64
blocks."""
from chipbench.layers.scan_kernel_roofline import compute  # noqa: F401
