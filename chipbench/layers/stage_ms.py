"""Shared by the readers of one dispatch-profiler stage: seconds over
count of `tempo_search_dispatch_stage_seconds{stage}` across the window,
in ms per launch, whatever the profiler's `mode`."""
from chipbench.lib import delta

NAME = "tempo_search_dispatch_stage_seconds"


def per_launch(run, stage: str):
    s = delta(run, NAME + "_sum", stage=stage)
    n = delta(run, NAME + "_count", stage=stage)
    return s / n * 1e3 if n else None
