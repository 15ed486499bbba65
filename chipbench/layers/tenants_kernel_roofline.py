"""Kernel: the least time the chip's HBM could take to read what the
scan launches of the traced seconds HAD to read, over the device time
they took. Not `scan_kernel_roofline`: that reckons a launch's bytes as
the tenant's pages over its groups, which holds for one tenant of equal
groups. Here a launch's bytes are `costs.scan_bytes` of its REAL pages:
the `blocks` its `dispatch.execute` span carries x the pages a block
holds (the manifest's), averaged over the launches of the traced
seconds, times the scan programs the trace counted. Pad pages and pad
rows are no bytes the scan had to read: padding reads as a lower share.
Bound: memory (819 GB/s, chipbench/peaks.json); no new kernel, the same
program at five sizes. A program whose `dispatch.execute` has no `blocks`
gives nothing to read."""
from chipbench import costs
from chipbench.layers.tenants import launches, pages_per_block
from chipbench.lib import scan_programs


def compute(run):
    trace = run.get("trace")
    seen = launches(run)
    if not trace or not seen:
        return None
    ns, n = scan_programs(trace)
    if not n:
        return None
    m = run["manifest"]
    n_vals = int(m["present"].sum(axis=1).max())
    pages = pages_per_block(m)
    per_launch = sum(
        costs.scan_bytes(s["attributes"]["blocks"] * pages,
                         m["kv_per_entry"], len(m["key_names"]), n_vals)
        for s in seen) / len(seen)
    return 100.0 * costs.roofline_s(n * per_launch, run["device_kind"]) / (
        ns / 1e9)
