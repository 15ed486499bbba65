"""What the launches of `highcard.substring` have to move, from their
staged shapes: `costs.py` for a tenant whose value ids are int32 and
whose dictionaries live in HBM. Kept with the benchmark, like `costs.py`,
and counted from the work done, whatever implements it.

A scan launch reads its group's columns once (`costs.scan_bytes`: at
more than 32,767 values a dictionary the value ids are 4 B a slot). A
launch that tests membership by a hit mask also reads the mask table it
is given: one byte for each term and value of each dictionary of the
group, `v_pad` values a dictionary (the power of two above the largest).
The gather's own traffic (a byte for every slot of every entry) is not
counted: how membership is looked up is the implementation's, and a share
may not count the same table twice.
"""

from __future__ import annotations

from chipbench import costs


def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def scan_bytes(pages: float, kv_slots: int, n_keys: int, n_vals: int,
               mask_members: int = 0, mask_dicts: int = 0,
               terms: int = 1) -> float:
    """Bytes one scan launch over `pages` staged pages must read; with
    `mask_members` members that each bring a hit mask over `mask_dicts`
    dictionaries and `terms` terms."""
    return (costs.scan_bytes(pages, kv_slots, n_keys, n_vals)
            + mask_members * mask_dicts * terms * _pow2(n_vals))

