"""D2H + merge: host wait at the one sync per dispatch: sum of the
dispatch profiler's `d2h` stage seconds over its count. Unfenced, so
the device's own time lands here too (observability/profile.py)."""
from chipbench.lib import delta

NAME = "tempo_search_dispatch_stage_seconds"


def compute(run):
    s = delta(run, NAME + "_sum", stage="d2h")
    n = delta(run, NAME + "_count", stage="d2h")
    return s / n * 1e3 if n else None
