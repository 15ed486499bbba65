"""Served path, client side: the median served `/api/search?agg=red`
latency, from due time, over every search of the window: what a panel
waits for. No end-to-end metric, since in a closed loop past the
device's capacity it is the queue's length."""
from chipbench.layers.sibling import compute_of

compute = compute_of("search_p50_ms.structural")
