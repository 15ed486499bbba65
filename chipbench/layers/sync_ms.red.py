"""D2H + merge: `sync_ms.scan` in the cell of RED dashboards: the host's
wait at the one sync a dispatch. Unfenced, so a launch's sorts land
here."""
from chipbench.layers.sibling import compute_of

compute = compute_of("sync_ms.scan")
