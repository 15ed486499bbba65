"""D2H + merge: `sync_ms.scan` in the cell whose groups are evicted: the
`d2h` stage of a launch, unfenced, so the device's own time and the
queue before it land here: a launch behind a 256 MB put waits for it."""
from chipbench.layers.sibling import compute_of

compute = compute_of("sync_ms.scan")
