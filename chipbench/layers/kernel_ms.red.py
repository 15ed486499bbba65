"""Kernel: `kernel_ms.scan` in the cell of RED dashboards: device time of
the scan programs per launch. A launch here carries the `?agg=`
reduction behind the scan (a sort of the group's whole key column and a
search of the key space's edges in it): 9 ms where `share16.scan`'s
reads 1.4."""
from chipbench.layers.sibling import compute_of

compute = compute_of("kernel_ms.scan")
