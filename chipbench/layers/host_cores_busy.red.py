"""Host process: `host_cores_busy` in the cell of RED dashboards: far
under 1.0 where the device is the limit and sixteen callers wait on
it."""
from chipbench.layers.sibling import compute_of

compute = compute_of("host_cores_busy")
