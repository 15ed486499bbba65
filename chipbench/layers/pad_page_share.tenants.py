"""Staging: pad pages among the pages staged for the groups the window
launched on. A group's staged pages are what its launches say
(`pages_per_shard` x `shards` on `coalescer.launch`, padding included:
the page axis is staged at a power of two), its real pages its `blocks`
x the pages a block holds (the manifest's); groups are told apart by
their block count, and the manifest's `group_blocks` says how many
groups of each count the tenants have. Groups no launch of the window
read are left out on both sides."""
from chipbench.layers.tenants import launches, pages_per_block


def compute(run):
    staged_of = {}
    for s in launches(run, "coalescer.launch", traced=False):
        a = s["attributes"]
        if a.get("pages_per_shard"):
            staged_of[int(a["blocks"])] = (
                int(a["pages_per_shard"]) * int(a.get("shards", 1)))
    m = run["manifest"]
    real = staged = 0.0
    for blocks in m.get("group_blocks", ()):
        if blocks in staged_of:
            real += blocks * pages_per_block(m)
            staged += staged_of[blocks]
    return 100.0 * (staged - real) / staged if staged else None
