"""Host process: `host_cores_busy` in the cell judged on
`search_p50_ms`: at 27 searches a second, how much of one core the
served path takes (the knee is where it nears 1)."""
from chipbench.layers.sibling import compute_of

compute = compute_of("host_cores_busy")
