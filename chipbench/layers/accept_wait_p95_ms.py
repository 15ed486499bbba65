"""HTTP surface: p95 over searches of `accept_wait_ms` on `http.request`:
connection taken on the accept thread -> the handler entered, so the
handler thread's start and the header parse."""
from chipbench.layers.spans import REQUEST, named, p95, searches


def compute(run):
    return p95([r["attributes"]["accept_wait_ms"]
                for spans in searches(run["spans"]).values()
                for r in named(spans, REQUEST)
                if "accept_wait_ms" in r["attributes"]])
