"""Kernel: the least time the chip's HBM could take to read what the
structural launches of the traced seconds HAD to read, over the device
time they took. A launch's bytes are `costs_structural.search_bytes` of
its query (the span columns its leaves read over the LIVE span rows, the
parent column where it joins, the segment and entry columns), the mean
over the window's completed searches, which come in the mix's shares,
over the launches a search makes (`groups` on `batcher.Search`), times
the scan programs the trace counted. Pad rows and a doubling join's
extra trips are no bytes: they read as a lower share. Bound: memory
(819 GB/s, chipbench/peaks.json): compares, gathers and prefix sums over
narrow integer lanes, no matrix unit work."""
from chipbench import costs, costs_structural
from chipbench.lib import scan_programs


def compute(run):
    trace = run.get("trace")
    groups = [s["attributes"].get("groups") for s in run["spans"]
              if s["name"] == "batcher.Search"
              and s["attributes"].get("groups")]
    done = [run["requests"][r["i"]]["ref"]["q"] for r in run["records"]
            if r["status"] == 200 and "q" in run["requests"][r["i"]].get(
                "ref", {})]
    if not trace or not groups or not done:
        return None
    ns, n = scan_programs(trace)
    if not n:
        return None
    m = run["manifest"]
    launches = max(groups)
    n_vals = int(m["present"].sum(axis=1).max())
    per_search = sum(costs_structural.search_bytes(
        q, m["spans"], m["entries"], m["span_slots"], m["kv_per_entry"],
        len(m["key_names"]), n_vals, launches) for q in done) / len(done)
    return 100.0 * costs.roofline_s(n * per_search / launches,
                                    run["device_kind"]) / (ns / 1e9)
