"""Batcher + coalescer: members of scan launches that tested membership
by a gather from a hit mask, among all
(`tempo_search_scan_membership_total{path}`): the share of the traffic
whose needles hit more than `dict_probe.R_MAX` runs of a dictionary."""
from chipbench.lib import delta

NAME = "tempo_search_scan_membership_total"


def compute(run):
    mask = delta(run, NAME, path="mask")
    n = mask + delta(run, NAME, path="range")
    return 100.0 * mask / n if n else None
