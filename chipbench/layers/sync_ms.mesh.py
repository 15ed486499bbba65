"""D2H + merge, on a mesh: host wait at the one sync per dispatch, as
`sync_ms.scan`: the dispatch profiler's `d2h` stage, seconds over count.
Unfenced, so the devices' own time, the collectives and the queue before
them land here too; the outputs are replicated, one device is read."""
from chipbench.layers.stage_ms import per_launch


def compute(run):
    return per_launch(run, "d2h")
