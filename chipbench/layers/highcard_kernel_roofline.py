"""Kernel: `scan_kernel_roofline` for a tenant of int32 value ids: the
least time the chip's HBM could take to read what the traced window's
scan launches had to read (chipbench/costs_highcard.py: a group's
columns a launch, and for the launches of `mask_scan_kernel` the hit
masks they were given), over the device time they took. Memory bound."""
from chipbench import costs, costs_highcard
from chipbench.lib import delta, scan_programs


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
    n_vals = int(m["present"].sum(axis=1).max())
    calls = trace["program_calls"]
    masked = sum(v for k, v in calls.items() if "mask_scan_kernel" in k)
    # members a mask launch, from the counters over the whole window
    members = delta(run, "tempo_search_scan_membership_total", path="mask")
    launches = sum(1 for s in run["spans"] if s["name"] == "dispatch.execute"
                   and s["attributes"].get("membership") == "mask")
    per_mask = members / launches if launches else 1.0
    blocks = len(m["block_ids"]) / max(groups)
    plain = costs_highcard.scan_bytes(
        m["pages"] / max(groups), m["kv_per_entry"], len(m["key_names"]),
        n_vals)
    mask = costs_highcard.scan_bytes(
        0, 0, 0, n_vals, mask_members=per_mask, mask_dicts=blocks)
    return 100.0 * costs.roofline_s(n * plain + masked * mask,
                                    run["device_kind"]) / (ns / 1e9)
