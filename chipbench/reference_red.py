"""The plain reference of `GET /api/search?agg=red`: what an aggregating
search must answer, from the generator's own arrays. numpy only; imports
nothing of the program.

Semantics (the program's documented contract, docs/search-analytics.md,
restated):
- the predicate is a plain search's (`chipbench/reference.py`: tag
  terms by substring, duration bounds, time window, all ANDed), and so
  is the header rollup: an aggregating search is not `exhaustive`, it
  skips the blocks a plain search would skip and inspects no entry of
  them;
- it never stops early: `limit` bounds the trace list, not the scan. So
  `inspectedTraces` is exact (the blocks not skipped x the entries of a
  block) and the aggregate is a function of the data alone;
- `aggregates.series` has one entry for each root service (the entry's
  `service.name`) with a matched entry: `calls` the matched entries,
  `errors` those that carry the pair `error=true`, `hist` fifteen counts
  of their durations against `EDGES_MS`: bin i holds the durations d
  with EDGES_MS[i-1] < d <= EDGES_MS[i] (`le` buckets, the bin
  `np.searchsorted(EDGES_MS, d, side="left")`), the last the durations
  over 16,384 ms. A service with no match is absent;
- the trace list: because the scan runs to the end, the list too is a
  function of the data: the whole match set where it has at most
  `limit` entries, else the `limit` latest starts. This was checked, not
  assumed: the program's collector keeps every group's top-k and cuts
  the sorted union at `limit` (`search/results.py response`), so which
  group drained first cannot change it (every template of the cell, solo
  and fused, `tests/test_red_served.py`). Had it not been, the list would
  be held as `ops/search.py` holds a search that may stop early (`limit`
  true matches).

`EDGES_MS` is upstream's span-metrics histogram,
`prometheus.ExponentialBuckets(0.002, 2, 14)` seconds
(`modules/generator/processor/spanmetrics/spanmetrics.go:34-88`),
written out in integer milliseconds: 0.002 s x 2^i, i = 0..13.

Counts are taken with `np.bincount` over a composite (service, bin,
error) index, block by block; no sort.
"""

from __future__ import annotations

import numpy as np

from chipbench import reference

EDGES_MS = (2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192,
            16384)
BINS = len(EDGES_MS) + 1


def aggregate(query: dict, corpus: dict, pool=None) -> dict:
    """{service: {"calls", "errors", "hist"}} over every entry of the
    tenant the predicate matches, in the blocks the rollup keeps."""
    terms = reference._terms(query, corpus)
    skip = reference.skipped_blocks(query, corpus, terms)
    table = corpus["table"]
    V = len(table)
    edges = np.asarray(EDGES_MS, dtype=np.int64)
    live = [b for b in range(len(skip)) if not skip[b]
            and all(col >= 0 for col, _ in terms)]

    def one(b: int) -> np.ndarray:
        flat = np.flatnonzero(reference._block_mask(query, corpus, terms, b))
        svc = corpus["root_service"][b][flat].astype(np.int64)
        bins = np.searchsorted(
            edges, corpus["dur"][b][flat].astype(np.int64), side="left")
        err = corpus["error"][b][flat].astype(np.int64)
        return np.bincount((svc * BINS + bins) * 2 + err,
                           minlength=V * BINS * 2)

    total = np.zeros(V * BINS * 2, dtype=np.int64)
    for part in (pool.map if pool is not None else map)(one, live):
        total += part
    counts = total.reshape(V, BINS, 2)
    series = {}
    for v in np.flatnonzero(counts.sum(axis=(1, 2))):
        sub = counts[v]
        series[table[v]] = {"calls": int(sub.sum()),
                            "errors": int(sub[:, 1].sum()),
                            "hist": [int(x) for x in sub.sum(axis=1)]}
    return series


def answer(query: dict, corpus: dict, pool=None) -> dict:
    """`reference.answer`'s trace list, held as a search that ran to the
    end, and the aggregate."""
    want = reference.answer(query, corpus, pool)
    want["deterministic"] = True
    want["aggregates"] = {"type": "red", "buckets_ms": list(EDGES_MS),
                          "series": aggregate(query, corpus, pool)}
    return want
