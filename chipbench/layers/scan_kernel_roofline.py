"""Kernel: the least time the chip's HBM could take to read what the
scan launches of the traced window had to read (chipbench/costs.py),
over the device time they took. Bound: memory (the scan is compares
and reductions over narrow integer lanes; no matrix unit work). Each
launch reads one staged group: the tenant's pages over the number of
groups the batcher planned (`groups` on the `batcher.Search` span)."""
from chipbench import costs
from chipbench.lib import scan_programs


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
    per_launch = costs.scan_bytes(
        m["pages"] / max(groups), m["kv_per_entry"], len(m["key_names"]),
        int(m["present"].sum(axis=1).max()))
    return 100.0 * costs.roofline_s(n * per_launch, run["device_kind"]) / (
        ns / 1e9)
