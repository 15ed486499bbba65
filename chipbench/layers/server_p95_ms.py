"""HTTP surface: p95 duration of a search's `http.request` span, accept
to last byte of the reply written. `search_p95_ms` less this is the
kernel's accept queue and the client."""
from chipbench.layers.spans import REQUEST, ms, named, p95, searches


def compute(run):
    return p95([ms(r) for spans in searches(run["spans"]).values()
                for r in named(spans, REQUEST)])
