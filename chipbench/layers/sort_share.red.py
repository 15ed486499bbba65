"""Kernel: what of the device's busy time in the traced seconds went to
sorting: the device time of the ops whose name starts with `sort`
(`trace["ops_ns"]`, named by the HLO op: `sort.3`), over the busy time.
The `?agg=` reduction counts by sorting the group's whole key column a
member (`multiblock.agg_entry_counts`); the scan's top-k sorts too, a
few thousand rows. The share a `perf_opt` on the reduction has to
move."""


def compute(run):
    trace = run.get("trace")
    if not trace or not trace.get("busy_ns"):
        return None
    busy = sum(d["busy_ns"] for d in trace["devices"])
    ns = sum(v for k, v in trace["ops_ns"] if k.startswith("sort"))
    return 100.0 * ns / busy if busy else None
