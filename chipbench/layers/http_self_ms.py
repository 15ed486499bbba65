"""HTTP surface: median self time of the `HTTP GET /api/search` span
(parse, route, merge render, JSON), children taken out."""
from chipbench.lib import median, self_times_ms


def compute(run):
    return median(self_times_ms(run["spans"], {"HTTP GET /api/search"}))
