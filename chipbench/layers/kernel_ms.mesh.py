"""Kernel, on a mesh: device time of the scan programs per launch and
device, from the profiler trace (chipbench/xplane.py). A mesh launch
runs once on every device plane; `lib.scan_programs` sums time and calls
over the planes alike, so the quotient is one shard's share of one
launch, its collectives and its wait for the slowest shard included."""
from chipbench.lib import scan_programs


def compute(run):
    if not run.get("trace"):
        return None
    ns, n = scan_programs(run["trace"])
    return ns / n / 1e6 if n else None
