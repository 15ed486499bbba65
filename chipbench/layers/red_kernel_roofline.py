"""Kernel: the least time the chip's HBM could take to move what the
reducing launches of the traced seconds HAD to move
(`costs_red.launch_bytes`: the scan's columns and the group's key column
once a launch, the dense counts once a member), over the device time
they took. A launch reads one staged group: the tenant's pages over the
groups the batcher planned (`groups` on `batcher.Search`, the largest: a
windowed search plans fewer); its members are the window's group scans
over its launches (`searches_per_dispatch`'s counters). The reduction's
own passes are no bytes: the share says how far above the memory bound
the sort runs, and a better way to count raises it. Bound: memory
(819 GB/s, chipbench/peaks.json): compares, a sort and a search over
narrow integer lanes, no matrix unit work."""
from chipbench import costs, costs_red
from chipbench.layers.sibling import compute_of
from chipbench.lib import scan_programs

members_a_launch = compute_of("searches_per_dispatch.scan")


def compute(run):
    trace = run.get("trace")
    groups = [s["attributes"].get("groups") for s in run["spans"]
              if s["name"] == "batcher.Search"
              and s["attributes"].get("groups")]
    if not trace or not groups:
        return None
    ns, n = scan_programs(trace)
    if not n:
        return None
    m = run["manifest"]
    per_launch = costs_red.launch_bytes(
        m["pages"] / max(groups), m["kv_per_entry"], len(m["key_names"]),
        int(m["present"].sum(axis=1).max()),
        len(m["vocab"]["services"]), members_a_launch(run) or 1.0)
    return 100.0 * costs.roofline_s(n * per_launch, run["device_kind"]) / (
        ns / 1e9)
