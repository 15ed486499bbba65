"""What a structural launch has to move, from the query and the staged
shapes: `costs.py` for a scan that reads span columns. Kept with the
benchmark, like `costs.py`, and counted from the work asked for,
whatever implements it.

A search over a tenant reads, once each however many leaves name them:
  - of every LIVE span row the columns its span-scope leaves read:
    `span_trace` (4 B, which rows are spans at all) always; the kv key
    and value ids of each slot (2 x 4 B x `span_slots`) and `span_block`
    (4 B, whose dictionary) where a leaf is a tag term; `span_dur` (4 B)
    where a leaf or a quantile reads a duration; `span_kind` (1 B) where
    a leaf is a kind;
  - the parent column (4 B a live row) once where the plan has a
    relation (`child`, `desc`);
  - the segment columns (`entry_span_begin`, `entry_span_count`: 8 B an
    entry) once where it has an aggregate (`exists`, `count`,
    `quantile`);
  - the entry columns as `costs.scan_bytes` counts them: start, end,
    duration and the valid flag (13 B an entry) always, the kv slots at
    the dictionaries' widths where a trace-scope leaf is a tag term;
  - the packed output of each launch (count, inspected, k scores, k
    indices: int32).
Pad rows of the span axis and the extra trips of a join that doubles
pointers count as no bytes: a better join, or a tighter pad, raises the
share, and nothing can push it past 100 %.
"""

from __future__ import annotations

from chipbench import costs

SPAN_TRACE = SPAN_BLOCK = SPAN_DUR = SPAN_PARENT = 4
SPAN_KIND = 1
SPAN_KV = 8             # key id + value id, int32 each, a slot
SEGMENT = 8             # entry_span_begin + entry_span_count
TOP_K = 128             # search/engine.py DEFAULT_TOP_K


def reads(q: dict) -> set:
    """What a query's plan reads: a set of `span.tag`, `span.dur`,
    `span.kind`, `relation`, `aggregate`, `trace.tag`."""
    out: set = set()

    def span(e):
        (op, v), = e.items()
        if op in ("tag", "dur", "kind"):
            out.add("span." + op)
        elif op in ("and", "or"):
            for a in v:
                span(a)
        elif op == "not":
            span(v)
        else:
            out.add("relation")
            for a in v.values():
                span(a)

    def trace(e):
        (op, v), = e.items()
        if op == "tag":
            out.add("trace.tag")
        elif op in ("and", "or"):
            for a in v:
                trace(a)
        elif op == "not":
            trace(v)
        elif op in ("child", "desc"):
            out.add("aggregate")
            span(e)
        elif op == "exists":
            out.add("aggregate")
            span(v)
        elif op in ("count", "quantile"):
            out.add("aggregate")
            if op == "quantile":
                out.add("span.dur")
            span(v["of"])

    trace(q)
    return out


def search_bytes(q: dict, spans: int, entries: int, span_slots: int,
                 kv_slots: int, n_keys: int, n_vals: int,
                 launches: int = 1) -> int:
    """Bytes the launches of one search over a tenant of `spans` live
    span rows and `entries` entries must move."""
    r = reads(q)
    row = 0
    if r & {"span.tag", "span.dur", "span.kind"}:
        row += SPAN_TRACE
    if "span.tag" in r:
        row += SPAN_KV * span_slots + SPAN_BLOCK
    if "span.dur" in r:
        row += SPAN_DUR
    if "span.kind" in r:
        row += SPAN_KIND
    if "relation" in r:
        row += SPAN_PARENT
    entry = costs.ENTRY_COLUMN_BYTES
    if "aggregate" in r:
        entry += SEGMENT
    if "trace.tag" in r:
        entry += kv_slots * (costs.id_width(n_keys) + costs.id_width(n_vals))
    return spans * row + entries * entry + launches * 4 * (2 + 2 * TOP_K)
