"""Kernel: `kernel_ms.scan` in the cell of many tenants: device time of
the scan programs per launch, the mean over launches of five sizes (a
group of 4 blocks to a full one of 64), most of them small."""
from chipbench.layers.sibling import compute_of

compute = compute_of("kernel_ms.scan")
