"""Kernel, on a mesh: the busiest device's busy time in the traced
window over the least busy one's (`trace["devices"][i]["busy_ns"]`):
1 when the shards carry the same work; a skewed split of the page axis
or one slow chip shows here, and every launch waits for the slowest."""


def compute(run):
    trace = run.get("trace")
    if not trace or len(trace.get("devices", ())) < 2:
        return None
    busy = [d["busy_ns"] for d in trace["devices"]]
    return max(busy) / min(busy) if min(busy) > 0 else None
