"""Batcher + coalescer: median over launches of how far the host ran
ahead of the device: a launch's `device.scan` start less its
`coalescer.launch` end, joined on the `launch` id. Zero when the device
was free at the enqueue. Host-observed, not a device measurement:
`device.scan` starts where the program's watcher thread stamped the
previous launch's outputs ready, which it does when it next gets the
GIL, so the value reads late by that lag (about a millisecond on a v5e,
against a queue of tens)."""
from chipbench.lib import median
from chipbench.layers.spans import DEVICE, named


def compute(run):
    enqueued = {s["attributes"].get("launch"): s["end_ns"]
                for s in named(run["spans"], "coalescer.launch")}
    return median([
        (d["start_ns"] - enqueued[d["attributes"]["launch"]]) / 1e6
        for d in named(run["spans"], DEVICE)
        if d["attributes"].get("launch") in enqueued])
