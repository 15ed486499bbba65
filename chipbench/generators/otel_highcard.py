"""Corpus generator `otel_highcard`: `otel_blocks`' tenant with one tag of
high cardinality, `customer.id` drawn from a domain of a million ids.

`generate(params, seed, backend_dir, pool) -> manifest`

Everything but that domain is `otel_blocks`': keys, their shares, the
other value domains and their laws, durations, times, block ids, trace
ids, the container (`pack_block`) and the manifest's arrays, all
imported. What differs, and why it is a file of its own: the table of
values passes 32,767, which `otel_blocks.vocabulary` refuses, so value
ids are int32 here (the program's `kv_val` too: `multiblock._narrow`).

`customer.id`: `customers` ids, each `cus_` + 7 lowercase letters, the
letters a fixed scramble of the id's rank (so an id says nothing of how
popular it is, any two letters after `cus_` open 1/676 of the ids, and a
run of letters from the middle of an id is in about 6/676 (two letters)
or 5/17,576 (three) of them, scattered over the sorted dictionary).
Entries draw an id by Zipf(`customer_zipf_s`) over the ranks.

A block's dictionary is the values its entries hold. The program packs a
dictionary for the device probe by the power of two above its size, and
that size is a jit shape of the probe kernel: blocks on both sides of a
power of two would compile every probe shape twice. So the generator
prints the smallest, median and largest dictionary of the corpus and
refuses one that straddles a power of two, and one whose smallest
dictionary is under `min_vals` (the program's floor for the device
probe, where the configuration states it).

The cell this generator feeds reports how a launch tests membership
(`tempo_search_scan_membership_total`). A program whose `/metrics` has
no such line is not driven: `generate` exits before it writes a block,
in `ops/search_aged.publishes`' words and for its reason. The one such
program (PR 33's parent) was run under this traffic on a v5e (PR 32):
every launch gathered from a hit mask, 0.46 s for 4,096 pages, set-up
took 215-297 s and a run 290-370 s of the 360 s it may take. A benchmark
that tries a new cell on the parent first needs a result or a refusal
from it, soon, and a run killed at the limit is neither.
"""

from __future__ import annotations

import json

import numpy as np

from chipbench.generators import otel_blocks as ob
from chipbench.ops.search_aged import publishes

MEMBERSHIP = "tempo_search_scan_membership_total"
ID_LETTERS = 7
ID_SPACE = 26 ** ID_LETTERS
# odd and not a multiple of 13: a bijection of the ranks modulo 26**7
# that moves every letter from one rank to the next
SCRAMBLE = 4_962_624_011


def _say(msg: str) -> None:
    import jax

    d = jax.devices()
    print(f"[platform={d[0].platform} kind={d[0].device_kind} n={len(d)}] "
          f"otel_highcard: {msg}", flush=True)


def customer_ids(lo: int, hi: int) -> list:
    """The ids of ranks lo..hi-1 (rank 0 the most popular). Ranks past
    the tenant's `customers` are well-formed ids that no block holds."""
    code = (np.arange(lo, hi, dtype=np.uint64) * np.uint64(SCRAMBLE)
            + np.uint64(7)) % np.uint64(ID_SPACE)
    raw = np.empty((hi - lo, 4 + ID_LETTERS), dtype=np.uint8)
    raw[:, :4] = np.frombuffer(b"cus_", dtype=np.uint8)
    for j in range(ID_LETTERS - 1, -1, -1):
        raw[:, 4 + j] = 97 + (code % np.uint64(26)).astype(np.uint8)
        code //= np.uint64(26)
    return raw.view(f"S{4 + ID_LETTERS}").ravel().astype(str).tolist()


def vocabulary(params: dict) -> dict:
    """`otel_blocks.vocabulary` with the customers' domain replaced."""
    vocab = ob.vocabulary(dict(params, customers=0))
    ids = customer_ids(0, int(params["customers"]))
    vocab["domains"]["customer.id"] = (
        ids, ob._zipf(len(ids), float(params["customer_zipf_s"])))
    vocab["table"] = sorted(
        {v for vals, _ in vocab["domains"].values() for v in vals})
    return vocab


def make_block(params: dict, vocab: dict, gid: dict, cum: dict, seed: int,
               index: int):
    """`otel_blocks.make_block` with int32 value ids and the cumulative
    laws made once (`cum`), not for every block."""
    n = int(params["entries_per_block"])
    rng = np.random.default_rng([seed % (1 << 32), seed >> 32, index])

    def draw(key):
        c = cum[key]
        return np.searchsorted(c, rng.random(n), side="right").clip(
            0, len(c) - 1)

    vals = np.full((n, len(ob.KEYS)), -1, dtype=np.int32)
    svc = draw("service.name")
    per = vocab["pods_per_service"]
    for c, (key, share) in enumerate(ob.KEYS):
        if key == "service.name":
            local = svc
        elif key == "k8s.namespace.name":
            local = svc // len(ob.ROLES)
        elif key == "k8s.pod.name":
            local = svc * per + draw("_pod")
        else:
            local = draw(key)
        col = gid[key][local]
        if share < 1.0:
            col = np.where(rng.random(n) < share, col, -1)
        vals[:, c] = col
    window = float(params["time_span_s"]) / int(params["blocks"])
    lo = params["time_base"] + index * window
    start = (lo + rng.random(n) * window * (1 + params["time_overlap"])
             ).astype(np.uint32)
    dur = np.exp(np.log(params["dur_median_ms"])
                 + params["dur_sigma"] * rng.standard_normal(n))
    dur = np.clip(dur, 1, 3_600_000).astype(np.uint32)
    end = start + (dur + 999) // 1000
    return vals, start, end.astype(np.uint32), dur


def check_dictionaries(sizes, min_vals=None) -> str:
    """The corpus's dictionary sizes in a line, or ValueError where they
    straddle a power of two or fall under the device probe's floor."""
    lo, hi = int(min(sizes)), int(max(sizes))
    line = (f"distinct values a block min={lo} median="
            f"{int(np.median(sizes))} max={hi}")
    if (lo - 1).bit_length() != (hi - 1).bit_length():
        raise ValueError(
            f"{line}: the blocks' dictionaries straddle a power of two, "
            "so the probe kernel would compile every shape twice")
    if min_vals is not None and lo < int(min_vals):
        raise ValueError(
            f"{line}: under the device probe's floor of {min_vals}, so "
            "some blocks would be probed on the host")
    return line


def require_membership(who: str) -> None:
    """Exit, in `who`'s name, on a program that does not publish how its
    launches test membership (the head of this file says why)."""
    if not publishes(MEMBERSHIP):
        raise SystemExit(
            f"{who}: this program's /metrics has no "
            f"{MEMBERSHIP}, the number its cell reads how a launch tests "
            "membership from; not run (chipbench/generators/"
            "otel_highcard.py says what happened when one was)")


def generate(params: dict, seed: int, backend_dir: str, pool) -> dict:
    require_membership("generator otel_highcard")
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.backend.types import (
        BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER,
    )
    from tempo_tpu.encoding.v2.compression import compress

    vocab = vocabulary(params)
    table = vocab["table"]
    index_of = {v: i for i, v in enumerate(table)}
    gid = {k: np.fromiter((index_of[v] for v in vals), dtype=np.int32,
                          count=len(vals))
           for k, (vals, _) in vocab["domains"].items()}
    cum = {k: np.cumsum(p) for k, (_, p) in vocab["domains"].items()
           if p is not None}
    cum["_pod"] = np.cumsum(ob._zipf(vocab["pods_per_service"], 1.1))
    n_blocks, n = int(params["blocks"]), int(params["entries_per_block"])
    n_pages = -(-n // ob.PAGE_ENTRIES)
    tenant = params["tenant"]
    be = LocalBackend(backend_dir)
    K = len(ob.KEYS)
    vals_all = np.empty((n_blocks, K, n), dtype=np.int32)  # key-major
    start_all = np.empty((n_blocks, n), dtype=np.uint32)
    end_all = np.empty((n_blocks, n), dtype=np.uint32)
    dur_all = np.empty((n_blocks, n), dtype=np.uint32)
    present_all = np.zeros((n_blocks, len(table)), dtype=bool)
    key_present_all = np.zeros((n_blocks, K), dtype=bool)
    ids = [ob.block_id(params["config_name"], i, n_pages)
           for i in range(n_blocks)]

    def one(i: int) -> tuple:
        vals, start, end, dur = make_block(params, vocab, gid, cum, seed, i)
        vals_all[i], start_all[i], end_all[i], dur_all[i] = (
            vals.T, start, end, dur)
        pages, present_all[i], key_present_all[i] = ob.pack_block(
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
        return (len(blob), hdr["kv_per_entry"], hdr["n_vals"],
                sum(len(v) for v in pages.val_dict))

    written = list(pool.map(one, range(n_blocks)))
    _say(check_dictionaries([w[2] for w in written], params.get("min_vals"))
         + f"; dictionary bytes a block median="
         f"{int(np.median([w[3] for w in written]))}; tenant-wide distinct "
         f"values={int(present_all.any(axis=0).sum())} of a table of "
         f"{len(table)}")
    return {
        "tenant": tenant, "blocks": {tenant: n_blocks}, "block_ids": ids,
        "entries": n_blocks * n, "pages": n_blocks * n_pages,
        "kv_per_entry": max(w[1] for w in written),
        "disk_bytes": sum(w[0] for w in written), "table": table,
        "key_names": ob.KEY_NAMES,
        "vals": vals_all, "start": start_all, "end": end_all,
        "dur": dur_all, "present": present_all,
        "key_present": key_present_all,
        "dict_bytes": [w[3] for w in written],
        "customers": int(params["customers"]),
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
