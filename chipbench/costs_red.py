"""What a launch that reduces (`?agg=red`) has to move, from its staged
shapes: `costs.py` for a scan with the aggregate behind it. Kept with
the benchmark, like `costs.py`, and counted from the work asked for,
whatever implements it.

A launch over one staged group must
  - read what `costs.scan_bytes` counts (the kv slots at the
    dictionaries' widths and the 13 B of entry columns, a staged entry),
    once a launch however many members fuse in it;
  - read the group's key column, 4 B a staged entry (the composite
    (service, duration bin, error) key the counts are taken over), once
    a launch however many members;
  - write the dense counts, 4 B x K a member (K the key space: the
    group's root services padded to a power of two x 15 bins x 2), and
    the packed rest of a member's row (count, inspected, k scores, k
    indices: int32).
The reduction's own passes (today a sort of the whole key column a
member and a search of K + 1 edges in it) count as no bytes: the share
reads the same work whatever implements it, a better way to count raises
it, and nothing can push it past 100 %.
"""

from __future__ import annotations

from chipbench import costs

KEY_BYTES = 4           # the staged composite key, int32 an entry
COUNT_BYTES = 4         # a dense count, int32
BINS = 15               # fourteen edges and +Inf
TOP_K = 128             # search/engine.py DEFAULT_TOP_K


def pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def key_space(services: int) -> int:
    """K: the services of a group and the slot of an unknown root,
    padded to a power of two, x bins x (ok, error)."""
    return pow2(services + 1) * BINS * 2


def launch_bytes(pages: int, kv_slots: int, n_keys: int, n_vals: int,
                 services: int, members: float = 1.0) -> float:
    """Bytes one reducing launch over `pages` staged pages must move for
    `members` fused members."""
    return (costs.scan_bytes(pages, kv_slots, n_keys, n_vals)
            + pages * costs.PAGE_ENTRIES * KEY_BYTES
            + members * COUNT_BYTES * (key_space(services)
                                       + 2 + 2 * TOP_K))
