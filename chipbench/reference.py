"""The plain reference: what a search must answer, from the generator's
own arrays. numpy only; imports nothing of the program.

Semantics (the program's documented contract, restated):
- a tag term `key=needle` matches an entry that has `key` with a value
  of which `needle` is a substring; terms AND together;
- `min_ms`/`max_ms` bound the duration, `start`/`end` the time window
  (an entry overlaps the window when its end >= start and its start <=
  end), all inclusive;
- a block is skipped, and its entries are not inspected, when its
  header rollup excludes the window or the duration bound, when a
  term's key is in no entry of the block, or when no value the block
  holds (under any key) contains a term's needle; an exhaustive request
  skips nothing;
- the answer holds at most `limit` traces. When fewer entries match
  than `limit`, or the request is exhaustive, the answer is a function
  of the data alone (`deterministic`): the whole match set, or the
  `limit` latest starts. Otherwise the engine may stop once `limit`
  results are in hand, and any `limit` true matches are a right answer.

A corpus is the generator's manifest: `vals` int16 [B, K, N] (global
value id per block, key and entry; -1 = the entry lacks the key),
`start`, `end`, `dur` [B, N], `present` bool [B, V], `key_present`
bool [B, K], `table` (the sorted global value strings), `key_names`.
"""

from __future__ import annotations

import numpy as np

KEEP_KEYS = 4096      # match sets up to this size are kept whole


def _terms(query: dict, corpus: dict):
    """[(key column or -1, bool [V] values containing the needle)]"""
    names = list(corpus["key_names"])
    out = []
    for k, needle in sorted(query.get("tags", {}).items()):
        hits = np.fromiter((needle in v for v in corpus["table"]),
                           dtype=bool, count=len(corpus["table"]))
        out.append((names.index(k) if k in names else -1, hits))
    return out


def skipped_blocks(query: dict, corpus: dict, terms=None) -> np.ndarray:
    """bool [B]: blocks the request does not inspect."""
    B = corpus["vals"].shape[0]
    if query.get("exhaustive"):
        return np.zeros(B, dtype=bool)
    terms = _terms(query, corpus) if terms is None else terms
    skip = np.zeros(B, dtype=bool)
    for col, hits in terms:
        if col < 0:
            return np.ones(B, dtype=bool)
        skip |= ~corpus["key_present"][:, col]
        skip |= ~(corpus["present"] & hits[None, :]).any(axis=1)
    if query.get("start"):
        skip |= corpus["end"].max(axis=1) < query["start"]
    if query.get("end"):
        skip |= corpus["start"].min(axis=1) > query["end"]
    if query.get("min_ms"):
        skip |= corpus["dur"].max(axis=1) < query["min_ms"]
    if query.get("max_ms"):
        skip |= corpus["dur"].min(axis=1) > query["max_ms"]
    return skip


def _block_mask(query: dict, corpus: dict, terms, b: int) -> np.ndarray:
    mask = np.ones(corpus["start"].shape[1], dtype=bool)
    for col, hits in terms:
        v = corpus["vals"][b, col]
        mask &= (v >= 0) & hits[np.maximum(v, 0)]
    if query.get("min_ms"):
        mask &= corpus["dur"][b] >= query["min_ms"]
    if query.get("max_ms"):
        mask &= corpus["dur"][b] <= query["max_ms"]
    if query.get("start"):
        mask &= corpus["end"][b] >= query["start"]
    if query.get("end"):
        mask &= corpus["start"][b] <= query["end"]
    return mask


def answer(query: dict, corpus: dict, pool=None) -> dict:
    """The reference answer to one request over the whole tenant."""
    terms = _terms(query, corpus)
    skip = skipped_blocks(query, corpus, terms)
    n = corpus["start"].shape[1]
    limit = int(query.get("limit") or 20)
    live = [b for b in range(len(skip)) if not skip[b]
            and all(col >= 0 for col, _ in terms)]

    def one(b: int):
        flat = np.flatnonzero(_block_mask(query, corpus, terms, b))
        starts = corpus["start"][b][flat]
        top = np.sort(starts)[::-1][:limit]
        keys = ((np.int64(b) << 32) | flat.astype(np.int64)
                if len(flat) <= KEEP_KEYS else None)
        return len(flat), keys, top

    parts = list((pool.map if pool is not None else map)(one, live))
    matches = sum(p[0] for p in parts)
    keys = None
    if matches <= KEEP_KEYS:
        keys = (np.sort(np.concatenate([p[1] for p in parts]))
                if parts else np.zeros(0, dtype=np.int64))
    tops = (np.sort(np.concatenate([p[2] for p in parts]))[::-1][:limit]
            if parts else np.zeros(0, dtype=np.uint32))
    return {
        "inspected": int((~skip).sum()) * n,
        "skipped_blocks": int(skip.sum()),
        "matches": int(matches), "keys": keys,
        "top_starts": [int(s) for s in tops], "limit": limit,
        "deterministic": bool(query.get("exhaustive")) or matches < limit,
    }


def entry_matches(query: dict, corpus: dict, block: int, flat: int,
                  terms=None) -> bool:
    """Whether one entry satisfies the request (any returned trace must)."""
    terms = _terms(query, corpus) if terms is None else terms
    if not (0 <= block < corpus["vals"].shape[0]
            and 0 <= flat < corpus["start"].shape[1]):
        return False
    for col, hits in terms:
        if col < 0:
            return False
        v = int(corpus["vals"][block, col, flat])
        if v < 0 or not hits[v]:
            return False
    dur = int(corpus["dur"][block, flat])
    if query.get("min_ms") and dur < query["min_ms"]:
        return False
    if query.get("max_ms") and dur > query["max_ms"]:
        return False
    if query.get("start") and int(corpus["end"][block, flat]) < query["start"]:
        return False
    if query.get("end") and int(corpus["start"][block, flat]) > query["end"]:
        return False
    return True
