"""Kernel: `kernel_ms.scan` in the cell of structural searches: device
time of the scan programs per launch: the mean over five plans, of which
the one that joins by ancestor (`desc`) weighs most."""
from chipbench.layers.sibling import compute_of

compute = compute_of("kernel_ms.scan")
