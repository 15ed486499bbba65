"""What the span readers share: spans by name, a search's spans by
trace id, durations and their p95. Every stamp is on the program's span
clock (`tracing.now_ns`); nothing is matched by wall clock. A program
without these spans gives every reader `None`."""
from chipbench.lib import percentile

SEARCH = "HTTP GET /api/search"
REQUEST = "http.request"
DEVICE = "device.scan"


def named(spans: list, name: str) -> list:
    return [s for s in spans if s["name"] == name]


def ms(s: dict) -> float:
    return (s["end_ns"] - s["start_ns"]) / 1e6


def p95(values: list):
    return percentile(values, 95) if values else None


def searches(spans: list) -> dict:
    """trace id -> the spans of that trace, for the traces of searches
    (a trace that holds the search route's span)."""
    ids = {s["trace_id"] for s in spans if s["name"] == SEARCH}
    out: dict = {t: [] for t in ids}
    for s in spans:
        if s["trace_id"] in out:
            out[s["trace_id"]].append(s)
    return out
