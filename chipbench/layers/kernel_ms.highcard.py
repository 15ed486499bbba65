"""Kernel: `kernel_ms.scan` in the cell of high-cardinality tags: device
time of the scan programs per launch, the mean over the launches that
only compare (`batch_scan_kernel`) and those that gather from a hit mask
(`mask_scan_kernel`); `scripts/trace_report.py` prints the two apart."""
from chipbench.layers.sibling import compute_of

compute = compute_of("kernel_ms.scan")
