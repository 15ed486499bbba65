"""Staging: pad rows among the span rows staged since the process
started (`tempo_search_structural_span_rows_total{kind=live|pad}`,
counted where a group's span columns are put on the device): what the
span axis costs for coming in powers of two, in HBM and in every pass a
launch makes over it. Since the start and not over the window: the cell
is resident and stages in set-up. A program without the counter gives
nothing to read."""
from chipbench.lib import metric_sum

NAME = "tempo_search_structural_span_rows_total"


def compute(run):
    after = run["counters"]["after"]
    pad = metric_sum(after, NAME, kind="pad")
    n = pad + metric_sum(after, NAME, kind="live")
    return 100.0 * pad / n if n else None
