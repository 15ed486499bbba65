"""Host process: `search_cpu_ms` in the cell of structural searches: CPU
milliseconds a search costs the host, `structural.compile` and the one
launch a search makes among them."""
from chipbench.layers.sibling import compute_of

compute = compute_of("search_cpu_ms")
