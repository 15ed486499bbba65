"""Batcher + coalescer, on a mesh: time a launch stood queued on the
process-wide collective dispatch lock (`parallel/mesh.py
locked_collective`): the dispatch profiler's `lock_wait` stage, seconds
over count across the window, per launch. Off a mesh the stage is never
booked and the reader finds nothing."""
from chipbench.layers.stage_ms import per_launch


def compute(run):
    return per_launch(run, "lock_wait")
