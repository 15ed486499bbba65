"""D2H + merge: `sync_ms.scan` in the cell of high-cardinality tags: the
`d2h` stage of a launch, unfenced, so a wait behind a launch that
gathers lands here."""
from chipbench.layers.sibling import compute_of

compute = compute_of("sync_ms.scan")
