"""D2H + merge: `sync_ms.scan` in the cell of many tenants: the `d2h`
stage of a launch, unfenced."""
from chipbench.layers.sibling import compute_of

compute = compute_of("sync_ms.scan")
