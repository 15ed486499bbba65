"""Batcher + coalescer: pad rows among the rows of the per-block query
tables the window's launches carried
(`tempo_search_launch_table_rows_total{kind=real|pad}`, once a launch and
member): what the block axis costs for coming in powers of two. A
program without the counter gives nothing to read."""
from chipbench.lib import delta

NAME = "tempo_search_launch_table_rows_total"


def compute(run):
    pad = delta(run, NAME, kind="pad")
    n = pad + delta(run, NAME, kind="real")
    return 100.0 * pad / n if n else None
