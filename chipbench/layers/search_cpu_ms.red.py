"""Host process: `search_cpu_ms` in the cell of RED dashboards: CPU
milliseconds a search costs the host, the decode of ten groups' counts
and the merges of its sub-answers among them."""
from chipbench.layers.sibling import compute_of

compute = compute_of("search_cpu_ms")
