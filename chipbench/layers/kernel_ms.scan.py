"""Kernel: device time of the scan programs per launch, from the
profiler trace (chipbench/xplane.py)."""
from chipbench.lib import scan_programs


def compute(run):
    if not run.get("trace"):
        return None
    ns, n = scan_programs(run["trace"])
    return ns / n / 1e6 if n else None
