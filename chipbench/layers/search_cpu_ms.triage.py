"""Host process: `search_cpu_ms` in the cell judged on
`search_p50_ms`."""
from chipbench.layers.sibling import compute_of

compute = compute_of("search_cpu_ms")
