"""Staging: bytes moved host -> device per completed search (query
tables always; page columns only when a group had to be re-staged)."""
from chipbench.lib import count_ok, delta


def compute(run):
    n = count_ok(run, "search")
    b = delta(run, "tempo_search_h2d_bytes_total")
    return b / n if n and b else None
