"""Kernel, on a mesh: the least time a chip's HBM could take to read
what the scan launches of the traced window had to read on each device
(chipbench/costs.py), over the device time they took, summed over the
device planes. Bound: memory, as `scan_kernel_roofline`. A launch reads
one staged group, and each device reads its shard of the page axis: the
tenant's pages over the groups the batcher planned (`groups` on the
`batcher.Search` span) over the shards (the trace's device planes: the
mesh is all the chips of the host). `program_calls` counts a launch once
on every plane, so calls x one shard's bytes is what all devices read."""
from chipbench import costs
from chipbench.lib import scan_programs


def compute(run):
    trace = run.get("trace")
    groups = [s["attributes"].get("groups") for s in run["spans"]
              if s["name"] == "batcher.Search"
              and s["attributes"].get("groups")]
    if not trace or not groups or not trace["devices"]:
        return None
    ns, n = scan_programs(trace)
    if not n:
        return None
    m = run["manifest"]
    per_shard = costs.scan_bytes(
        m["pages"] / max(groups) / len(trace["devices"]), m["kv_per_entry"],
        len(m["key_names"]), int(m["present"].sum(axis=1).max()))
    return 100.0 * costs.roofline_s(n * per_shard, run["device_kind"]) / (
        ns / 1e9)
