"""Kernel: `kernel_ms.scan` in the cell whose groups are evicted: the
same scan program at 24 groups of 64 blocks."""
from chipbench.layers.sibling import compute_of

compute = compute_of("kernel_ms.scan")
