"""The plain reference for structural searches: which traces a query over
span trees must answer, from the generator's own span arrays. numpy
only; imports nothing of the program.

The query is the JSON the client sends in `?q=` (the program's
documented form, docs/search-structural-queries.md, restated):

  trace level  {"tag": {"k", "v"}}        the entry's rolled-up kvs
               {"dur": {"min_ms", "max_ms"}}   the entry's duration
               {"exists": <span>}          at least one span matches
               {"count": {"of": <span>, "op", "n"}}
               {"quantile": {"of": <span>, "q", "op", "ms"}}
               {"and": [...]}, {"or": [...]}, {"not": ...}
               a bare {"child": ...} / {"desc": ...} is `exists` of it
  span level   {"tag": {"k", "v"}}, {"dur": {...}}, {"kind": name | 0-5}
               {"child": {"parent": <span>, "child": <span>}}   spans that
                   match `child` whose direct parent matches `parent`
               {"desc": {"anc": <span>, "span": <span>}}   spans that match
                   `span` with some proper ancestor matching `anc`
               {"and": [...]}, {"or": [...]}, {"not": ...}

Semantics: a tag term matches where the key is there with a value of
which `v` is a substring (an empty `v`: the key is there), as
`reference.py` has it; ranges are inclusive; a quantile is the published
nearest-rank one: of the m matched spans' durations SORTED, the
ceil(q m)-th, compared to `ms`; no matched span, no match. A structural
search prunes no block by its span terms and a trace of more spans than
the tenant's cap is judged on the spans it kept (the generator's arrays
hold those only).

How each is computed here, on purpose not the program's way: leaves are
boolean columns over the block's spans; `child` is one index through the
parent column; `desc` walks every span's parents upward until no walker
is left (a loop over depth, no doubling); counts are `np.add.reduceat`
over the traces' runs; a quantile sorts each trace's matched durations.

A block is a dict: `vals` int [K, N] and `dur` [N] (trace level),
`span_count` [N], `span_parent` [S] (index within the block, -1 root),
`span_dur`, `span_kind` [S], `span_vals` int [S, Ks] (-1 = no such key);
a corpus is the generator's manifest, which holds them by block beside
`table`, `key_names` and `span_key_names`.
"""

from __future__ import annotations

from fractions import Fraction

import numpy as np

KEEP_KEYS = 4096      # match sets up to this size are kept whole
KINDS = {"unspecified": 0, "internal": 1, "server": 2, "client": 3,
         "producer": 4, "consumer": 5}
CMP = {">": np.greater, ">=": np.greater_equal, "<": np.less,
       "<=": np.less_equal, "==": np.equal, "!=": np.not_equal}
U32 = 0xFFFFFFFF


def block_of(corpus: dict, b: int) -> dict:
    return {"vals": corpus["vals"][b], "dur": corpus["dur"][b],
            "span_count": corpus["span_count"][b],
            "span_parent": corpus["span_parent"][b],
            "span_dur": corpus["span_dur"][b],
            "span_kind": corpus["span_kind"][b],
            "span_vals": corpus["span_vals"][b]}


class _Eval:
    def __init__(self, corpus: dict, block: dict):
        self.table = corpus["table"]
        self.keys = list(corpus["key_names"])
        self.span_keys = list(corpus["span_key_names"])
        self.hits = corpus.setdefault("_structural_hits", {})
        self.b = block
        count = np.asarray(block["span_count"], dtype=np.int64)
        self.count = count
        self.begin = np.concatenate([[0], np.cumsum(count)])[:-1]
        self.S = int(count.sum())

    def _hit(self, needle: str) -> np.ndarray:
        """bool [V]: the values that contain the needle."""
        if needle not in self.hits:
            self.hits[needle] = np.fromiter(
                (needle in v for v in self.table), dtype=bool,
                count=len(self.table))
        return self.hits[needle]

    def _tag(self, col: np.ndarray, needle: str) -> np.ndarray:
        return (col >= 0) & self._hit(needle)[np.maximum(col, 0)]

    @staticmethod
    def _range(doc: dict, a: np.ndarray) -> np.ndarray:
        return (a >= int(doc.get("min_ms", 0))) & (
            a <= int(doc.get("max_ms", U32)))

    # ---- span level: bool [S] ----
    def span(self, e: dict) -> np.ndarray:
        (op, v), = e.items()
        if op == "tag":
            if v["k"] not in self.span_keys:
                return np.zeros(self.S, dtype=bool)
            return self._tag(
                self.b["span_vals"][:, self.span_keys.index(v["k"])],
                v.get("v", ""))
        if op == "dur":
            return self._range(v, self.b["span_dur"])
        if op == "kind":
            k = KINDS[v.lower()] if isinstance(v, str) else int(v)
            return self.b["span_kind"] == k
        if op == "and":
            return np.logical_and.reduce([self.span(a) for a in v])
        if op == "or":
            return np.logical_or.reduce([self.span(a) for a in v])
        if op == "not":
            return ~self.span(v)
        par = self.b["span_parent"]
        if op == "child":
            pm, cm = self.span(v["parent"]), self.span(v["child"])
            return cm & (par >= 0) & pm[np.maximum(par, 0)]
        if op == "desc":
            am, sm = self.span(v["anc"]), self.span(v["span"])
            out = np.zeros(self.S, dtype=bool)
            who = np.flatnonzero(sm)          # the walkers, and
            at = par[who]                     # where each stands
            while len(who):
                up = at >= 0
                who, at = who[up], at[up]
                found = am[at]
                out[who[found]] = True
                who, at = who[~found], par[at[~found]]
            return out
        raise ValueError(f"unknown span operator {op!r}")

    def per_trace(self, m: np.ndarray) -> np.ndarray:
        """Matched spans of each trace: a sum over its run."""
        if not self.S:
            return np.zeros(len(self.count), dtype=np.int64)
        # reduceat gives a[i] for an empty run: those traces are 0
        sums = np.add.reduceat(m.astype(np.int64),
                               np.minimum(self.begin, self.S - 1))
        return np.where(self.count > 0, sums, 0)

    # ---- trace level: bool [N] ----
    def trace(self, e: dict) -> np.ndarray:
        (op, v), = e.items()
        if op == "tag":
            if v["k"] not in self.keys:
                return np.zeros(len(self.count), dtype=bool)
            return self._tag(self.b["vals"][self.keys.index(v["k"])],
                             v.get("v", ""))
        if op == "dur":
            return self._range(v, self.b["dur"])
        if op in ("child", "desc"):
            return self.per_trace(self.span(e)) > 0
        if op == "exists":
            return self.per_trace(self.span(v)) > 0
        if op == "count":
            return CMP[v.get("op", ">")](self.per_trace(self.span(v["of"])),
                                         int(v.get("n", 0)))
        if op == "quantile":
            return self.quantile(v)
        if op == "and":
            return np.logical_and.reduce([self.trace(a) for a in v])
        if op == "or":
            return np.logical_or.reduce([self.trace(a) for a in v])
        if op == "not":
            return ~self.trace(v)
        raise ValueError(f"unknown trace operator {op!r}")

    def quantile(self, v: dict) -> np.ndarray:
        """Nearest rank: each trace's matched durations sorted, the
        ceil(q m)-th of them against `ms`."""
        q = Fraction(str(v["q"]))
        m = self.span(v["of"])
        n = self.per_trace(m)
        which = np.flatnonzero(m)
        trace = np.repeat(np.arange(len(self.count)), self.count)[which]
        durs = self.b["span_dur"][which].astype(np.int64)
        order = np.lexsort((durs, trace))
        durs = durs[order]
        first = np.concatenate([[0], np.cumsum(n)])[:-1]
        # ceil(q m), in whole numbers: q is a decimal the client wrote
        rank = np.maximum(1, -(-(q.numerator * n) // q.denominator))
        has = n > 0
        value = np.zeros(len(n), dtype=np.int64)
        value[has] = durs[first[has] + rank[has] - 1]
        return has & CMP[v.get("op", ">=")](value, int(v["ms"]))


def evaluate(expr: dict, corpus: dict, block: dict) -> np.ndarray:
    """bool [N]: the traces of one block the query matches."""
    return _Eval(corpus, block).trace(expr)


def span_counts(of: dict, corpus: dict, block: dict) -> np.ndarray:
    """int [N]: the spans of each trace of one block that match `of`."""
    ev = _Eval(corpus, block)
    return ev.per_trace(ev.span(of))


def verdicts(query: dict, corpus: dict, pool=None) -> list:
    """bool [N] a block, memoised on the corpus by the query."""
    import json

    memo = corpus.setdefault("_structural_verdicts", {})
    key = json.dumps(query["q"], sort_keys=True)
    if key not in memo:
        blocks = range(len(corpus["span_count"]))
        memo[key] = list((pool.map if pool is not None else map)(
            lambda b: evaluate(query["q"], corpus, block_of(corpus, b)),
            blocks))
    return memo[key]


def answer(query: dict, corpus: dict, pool=None) -> dict:
    """The reference answer to one request (`q`, `limit`, `exhaustive`)
    over the whole tenant, in `reference.answer`'s form. Span terms skip
    no block, so every entry is inspected."""
    limit = int(query.get("limit") or 20)
    masks = verdicts(query, corpus, pool)
    n = corpus["start"].shape[1]
    flats = [np.flatnonzero(mask) for mask in masks]
    matches = sum(len(f) for f in flats)
    keys = None
    if matches <= KEEP_KEYS:
        keys = np.sort(np.concatenate(
            [(np.int64(b) << 32) | f.astype(np.int64)
             for b, f in enumerate(flats)] or [np.zeros(0, dtype=np.int64)]))
    tops = np.sort(np.concatenate(
        [np.sort(corpus["start"][b][f])[::-1][:limit]
         for b, f in enumerate(flats)]
        or [np.zeros(0, dtype=np.uint32)]))[::-1][:limit]
    return {
        "inspected": len(masks) * n, "matches": int(matches), "keys": keys,
        "top_starts": [int(s) for s in tops], "limit": limit,
        "deterministic": bool(query.get("exhaustive")) or matches < limit,
    }


def entry_matches(query: dict, corpus: dict, block: int, flat: int) -> bool:
    masks = verdicts(query, corpus)
    return (0 <= block < len(masks) and 0 <= flat < len(masks[block])
            and bool(masks[block][flat]))
