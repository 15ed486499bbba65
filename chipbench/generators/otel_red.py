"""Corpus generator `otel_red`: `otel_blocks`' tenant with the pair
`error=true` on the entries whose root span ended in an error status.

`generate(params, seed, backend_dir, pool) -> manifest`

Everything of a block is `otel_blocks`': the sixteen keys and their
shares, the value domains and their laws, durations, times, block ids
(`block_id`) and trace ids (`make_block` draws the same streams, so an
entry of seed s and block i has the values it has there). What this file
adds is the seventeenth key, as this system's ingest writes it after
upstream's extraction (`tempo_tpu/search/data.py`, role of
`modules/distributor/search_data.go:28-88`: a root span whose status is
an error leaves `error=true` among the entry's pairs):

  - an entry whose `http.status_code` is 5xx (500, 502, 503: 1.3 % by
    `otel_blocks.STATUS`) carries `error=true`, every other entry has no
    `error` key;
  - the slot axis stays 16: an entry that would hold 17 pairs (all
    sixteen keys and the error pair) gives up `user.tier`, the last in
    key order, as an ingester with 16 kv slots drops the pair that does
    not fit. `user.tier` is asked by no template of the cell.

The dictionaries gain one key (`error`, sorted between
`deployment.environment` and `http.method`) and one value (`true`,
which contains no needle a template asks and is contained in none).

The manifest is `otel_blocks`' over seventeen key columns, plus what
`chipbench/reference_red.py` groups by: `error` bool [B, N] (the entry
carries the pair) and `root_service` int16 [B, N] (the global value id of
the entry's `service.name`, which the container stores as the entry's
root service); both are views of `vals`.

The cell this generator feeds reads counters and spans that a program
before PR 48 lacks (`tempo_search_agg_*`, `analytics.decode`); such a
program serves `?agg=red` all the same (the reduction is older than its
counters), so it is driven like any other and the readers of what it
lacks return nothing: `chipbench/ops/search_red.py` says what was
measured on one.
"""

from __future__ import annotations

import json

import numpy as np

from chipbench.generators import otel_blocks as ob

ERROR_KEY, ERROR_VALUE = "error", "true"
KEY_NAMES = tuple(sorted(ob.KEY_NAMES + (ERROR_KEY,)))
ERROR_COL = KEY_NAMES.index(ERROR_KEY)
SERVICE_COL = KEY_NAMES.index("service.name")
GIVES_WAY = KEY_NAMES.index("user.tier")
KV_SLOTS = 16
# the statuses that make a root span an error: HTTP 5xx
ERROR_STATUS = tuple(s for s, _ in ob.STATUS if s.startswith("5"))


def prepare(params: dict) -> tuple:
    """(vocab, table, gid): `otel_blocks`' vocabulary with `true` in the
    value table."""
    vocab = ob.vocabulary(params)
    table = sorted(set(vocab["table"]) | {ERROR_VALUE})
    index_of = {v: i for i, v in enumerate(table)}
    gid = {k: np.array([index_of[v] for v in vals], dtype=np.int16)
           for k, (vals, _) in vocab["domains"].items()}
    gid[ERROR_KEY] = np.array([index_of[ERROR_VALUE]], dtype=np.int16)
    return vocab, table, gid


def make_block(params: dict, vocab: dict, gid: dict, seed: int, index: int):
    """`otel_blocks.make_block` with the error column: `vals` int16
    [N, 17] in `KEY_NAMES` order."""
    base, start, end, dur = ob.make_block(params, vocab, gid, seed, index)
    status = base[:, ob.KEY_NAMES.index("http.status_code")]
    is_error = np.isin(status, [gid["http.status_code"][
        [s for s, _ in ob.STATUS].index(code)] for code in ERROR_STATUS])
    vals = np.full((base.shape[0], len(KEY_NAMES)), -1, dtype=np.int16)
    for c, key in enumerate(ob.KEY_NAMES):
        vals[:, KEY_NAMES.index(key)] = base[:, c]
    vals[is_error, ERROR_COL] = gid[ERROR_KEY][0]
    full = (vals >= 0).sum(axis=1) > KV_SLOTS
    vals[full, GIVES_WAY] = -1
    return vals, start, end, dur


def pack_block(vals, start, end, dur, table, block: int):
    """The arrays in the program's container, as `otel_blocks.pack_block`
    packs them, over this file's keys: slots filled in sorted-key order,
    per-block sorted dictionaries, header rollups, the entry's root
    service its `service.name`."""
    from tempo_tpu.search.columnar import ColumnarPages, PageGeometry

    n, _K = vals.shape
    E = ob.PAGE_ENTRIES
    P = -(-n // E)
    have = vals >= 0
    C = 1
    while C < int(have.sum(axis=1).max()):
        C *= 2
    present = np.zeros(len(table), dtype=bool)
    present[vals[have]] = True
    remap = (np.cumsum(present) - 1).astype(np.int32)
    key_present = have.any(axis=0)
    key_remap = (np.cumsum(key_present) - 1).astype(np.int32)
    slot = np.cumsum(have, axis=1) - 1
    rows, cols = np.nonzero(have)
    kv_key = np.full((P * E, C), -1, dtype=np.int32)
    kv_val = np.full((P * E, C), -1, dtype=np.int32)
    kv_key[rows, slot[rows, cols]] = key_remap[cols]
    kv_val[rows, slot[rows, cols]] = remap[vals[rows, cols]]
    valid = np.zeros(P * E, dtype=bool)
    valid[:n] = True

    def paged(a, dtype):
        out = np.zeros(P * E, dtype=dtype)
        out[:n] = a
        return out.reshape(P, E)

    header = {
        "n_entries": n, "n_pages": P, "entries_per_page": E,
        "kv_per_entry": C, "n_keys": int(key_present.sum()),
        "n_vals": int(present.sum()), "truncated_entries": 0,
        "min_start_s": int(start.min()), "max_end_s": int(end.max()),
        "min_dur_ms": int(dur.min()), "max_dur_ms": int(dur.max()),
    }
    pages = ColumnarPages(
        geometry=PageGeometry(E, C),
        key_dict=[k for k, p in zip(KEY_NAMES, key_present) if p],
        val_dict=[table[i] for i in np.flatnonzero(present)],
        kv_key=kv_key.reshape(P, E, C), kv_val=kv_val.reshape(P, E, C),
        entry_start=paged(start, np.uint32), entry_end=paged(end, np.uint32),
        entry_dur=paged(dur, np.uint32), entry_valid=valid.reshape(P, E),
        entry_root_svc=paged(remap[vals[:, SERVICE_COL]], np.int32),
        entry_root_name=paged(remap[vals[:, KEY_NAMES.index("name")]],
                              np.int32),
        trace_ids=ob.trace_ids(block, P), n_entries=n, header=header)
    return pages, present, key_present


def generate(params: dict, seed: int, backend_dir: str, pool) -> dict:
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.backend.types import (
        BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER,
    )
    from tempo_tpu.encoding.v2.compression import compress

    vocab, table, gid = prepare(params)
    n_blocks, n = int(params["blocks"]), int(params["entries_per_block"])
    n_pages = -(-n // ob.PAGE_ENTRIES)
    tenant = params["tenant"]
    be = LocalBackend(backend_dir)
    K = len(KEY_NAMES)
    vals_all = np.empty((n_blocks, K, n), dtype=np.int16)  # key-major
    start_all = np.empty((n_blocks, n), dtype=np.uint32)
    end_all = np.empty((n_blocks, n), dtype=np.uint32)
    dur_all = np.empty((n_blocks, n), dtype=np.uint32)
    present_all = np.zeros((n_blocks, len(table)), dtype=bool)
    key_present_all = np.zeros((n_blocks, K), dtype=bool)
    ids = [ob.block_id(params["config_name"], i, n_pages)
           for i in range(n_blocks)]

    def one(i: int) -> tuple:
        vals, start, end, dur = make_block(params, vocab, gid, seed, i)
        vals_all[i], start_all[i], end_all[i], dur_all[i] = (
            vals.T, start, end, dur)
        pages, present_all[i], key_present_all[i] = pack_block(
            vals, start, end, dur, table, i)
        blob = compress(pages.to_bytes(), "zstd")
        hdr = dict(pages.header)
        hdr["encoding"] = "zstd"
        hdr["compressed_size"] = len(blob)
        m = BlockMeta(tenant_id=tenant, encoding="zstd", block_id=ids[i],
                      start_time=hdr["min_start_s"],
                      end_time=hdr["max_end_s"])
        m.search_pages = hdr["n_pages"]
        m.search_size = len(blob)
        m.search_entries_per_page = hdr["entries_per_page"]
        m.search_kv_per_entry = hdr["kv_per_entry"]
        m.total_objects = hdr["n_entries"]
        be.write(tenant, m.block_id, NAME_SEARCH, blob)
        be.write(tenant, m.block_id, NAME_SEARCH_HEADER,
                 json.dumps(hdr).encode())
        be.write_block_meta(m)
        return len(blob), hdr["kv_per_entry"]

    written = list(pool.map(one, range(n_blocks)))
    return {
        "tenant": tenant, "blocks": {tenant: n_blocks}, "block_ids": ids,
        "entries": n_blocks * n, "pages": n_blocks * n_pages,
        "kv_per_entry": max(c for _, c in written),
        "disk_bytes": sum(n for n, _ in written), "table": table,
        "key_names": KEY_NAMES,
        "vals": vals_all, "start": start_all, "end": end_all,
        "dur": dur_all, "present": present_all,
        "key_present": key_present_all,
        "error": vals_all[:, ERROR_COL, :] >= 0,
        "root_service": vals_all[:, SERVICE_COL, :],
        "vocab": {"services": vocab["services"], "teams": list(ob.TEAMS),
                  "roles": list(ob.ROLES),
                  "domains": {k: (vals, None if p is None else p.tolist())
                              for k, (vals, p) in vocab["domains"].items()}},
        "dur_ms_quantile": lambda q: ob.duration_ms_quantile(
            params, float(q)),
        "time_base": params["time_base"],
        "time_span_s": params["time_span_s"],
        "entry_of_trace_id": ob.entry_of_trace_id,
    }
