"""Corpus generator `otel_tenants`: `otel_blocks`' corpus held by many
tenants whose sizes follow a Zipf law.

`generate(params, seed, backend_dir, pool) -> manifest`

Everything of a block is `otel_blocks`': keys, their shares, the value
domains and their laws, durations, the container (`pack_block`), block
ids (`block_id`, by the block's index in the whole corpus) and trace ids
(which carry that corpus-wide index, so an answer that held another
tenant's entry would name it). What this file adds is who owns which
block:

  tenants          how many (32)
  tenant_zipf_s    the law of their sizes and of their traffic (1.1)
  tenant_blocks    the sizes, heaviest first, as the configuration
                   states them; they must be `blocks` dealt over the
                   tenants by that law, largest remainder first
                   (`tenant_sizes`), or the run stops
  tenant_prefix    tenant ids are the prefix and the rank, `t01` the
                   heaviest

Blocks are laid out tenant-major: the heaviest tenant's first. Each
tenant's blocks cover the corpus's `time_span_s` in order, as a tenant's
own day of data does, and draw from a seed stream of the tenant's own.

The manifest's arrays are `otel_blocks`', over all blocks; `blocks` is
tenant -> count, which the harness's wait for the poll takes as it is;
`tenants` the ids in rank order, `tenant_law` the law, `tenant_slice`
tenant -> (first block, end), and `tenant_class` the class the traffic
mix groups tenants by: the power of two above the block count of the
tenant's LAST group (`last_group_bucket`), which restates the program's
grouping (a group closes at `max_batch_pages` pages, so at 64 blocks of
64 pages, with ids that step past the cut anchors: `otel_blocks.block_id`)
and is printed beside the groups the program says it staged. The plain
reference sees one tenant at a time: `view(manifest, tenant)` is the
manifest cut to that tenant's blocks, its trace ids mapped back from the
corpus-wide index (an id of another tenant's block maps to nothing).

The cell this generator feeds counts the scan program's jit keys and the
pad rows of its tables. A program whose `/metrics` has neither gauge nor
counter is driven all the same (no refusal as `otel_highcard` has one):
`chipbench/ops/search_tenant.py` says what was measured on one.
"""

from __future__ import annotations

import json

import numpy as np

from chipbench.costs_highcard import _pow2
from chipbench.generators import otel_blocks as ob

MAX_BATCH_PAGES = 4096    # the shipped search_max_batch_pages, off a mesh


def tenant_sizes(blocks: int, tenants: int, s: float) -> list:
    """`blocks` dealt over `tenants` by Zipf(s), largest remainder
    first; heaviest first."""
    want = ob._zipf(tenants, s) * blocks
    sizes = np.floor(want).astype(int)
    for i in np.argsort(-(want - sizes), kind="stable")[:blocks - sizes.sum()]:
        sizes[i] += 1
    return [int(n) for n in sizes]


def group_sizes(n_blocks: int, n_pages: int) -> list:
    """Block counts of the groups a tenant of `n_blocks` is scanned in:
    full groups of the page cap, then what is left."""
    per = max(1, MAX_BATCH_PAGES // max(1, n_pages))
    return [per] * (n_blocks // per) + (
        [n_blocks % per] if n_blocks % per else [])


def last_group_bucket(n_blocks: int, n_pages: int) -> int:
    return _pow2(group_sizes(n_blocks, n_pages)[-1])


def tenant_ids(params: dict) -> list:
    n = int(params["tenants"])
    width = len(str(n))
    return [f"{params.get('tenant_prefix', 't')}{r:0{width}d}"
            for r in range(1, n + 1)]


def view(manifest: dict, tenant: str) -> dict:
    """The manifest as the plain reference needs it for ONE tenant: the
    arrays cut to its blocks (views, nothing copied), answers' trace ids
    taken from the corpus-wide block index to the tenant's own. Kept on
    the manifest: the reference's answers memoise on the view."""
    views = manifest.setdefault("_views", {})
    if tenant not in views:
        lo, hi = manifest["tenant_slice"][tenant]

        def to_entry(hex_id: str):
            e = ob.entry_of_trace_id(hex_id)
            if e is None or not lo <= e[0] < hi:
                return None     # no entry of this tenant
            return e[0] - lo, e[1]

        v = {k: manifest[k] for k in (
            "table", "key_names", "vocab", "dur_ms_quantile", "time_base",
            "time_span_s")}
        for k in ("vals", "start", "end", "dur", "present", "key_present"):
            v[k] = manifest[k][lo:hi]
        v.update(tenant=tenant, entry_of_trace_id=to_entry)
        views[tenant] = v
    return views[tenant]


def generate(params: dict, seed: int, backend_dir: str, pool) -> dict:
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.backend.types import (
        BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER,
    )
    from tempo_tpu.encoding.v2.compression import compress

    n_blocks, n = int(params["blocks"]), int(params["entries_per_block"])
    n_pages = -(-n // ob.PAGE_ENTRIES)
    s = float(params["tenant_zipf_s"])
    tenants = tenant_ids(params)
    sizes = tenant_sizes(n_blocks, len(tenants), s)
    stated = params.get("tenant_blocks")
    if stated is not None and [int(x) for x in stated] != sizes:
        raise ValueError(
            f"tenant_blocks {stated} is not {n_blocks} blocks dealt over "
            f"{len(tenants)} tenants by Zipf({s}): {sizes}")
    if min(sizes) < 1:
        raise ValueError(f"a tenant without a block: {sizes}")
    first = np.concatenate([[0], np.cumsum(sizes)]).astype(int)
    owner = np.repeat(np.arange(len(tenants)), sizes)

    vocab = ob.vocabulary(params)
    table = vocab["table"]
    index_of = {v: i for i, v in enumerate(table)}
    gid = {k: np.array([index_of[v] for v in vals], dtype=np.int16)
           for k, (vals, _) in vocab["domains"].items()}
    be = LocalBackend(backend_dir)
    K = len(ob.KEYS)
    vals_all = np.empty((n_blocks, K, n), dtype=np.int16)  # key-major
    start_all = np.empty((n_blocks, n), dtype=np.uint32)
    end_all = np.empty((n_blocks, n), dtype=np.uint32)
    dur_all = np.empty((n_blocks, n), dtype=np.uint32)
    present_all = np.zeros((n_blocks, len(table)), dtype=bool)
    key_present_all = np.zeros((n_blocks, K), dtype=bool)
    ids = [ob.block_id(params["config_name"], i, n_pages)
           for i in range(n_blocks)]

    def one(i: int) -> tuple:
        t = int(owner[i])
        # the tenant's own day and its own stream: block j of its
        # sizes[t], drawn from a seed no other tenant shares
        vals, start, end, dur = ob.make_block(
            dict(params, blocks=sizes[t]), vocab, gid,
            seed + ((t + 1) << 40), i - int(first[t]))
        vals_all[i], start_all[i], end_all[i], dur_all[i] = (
            vals.T, start, end, dur)
        pages, present_all[i], key_present_all[i] = ob.pack_block(
            vals, start, end, dur, table, i)
        blob = compress(pages.to_bytes(), "zstd")
        hdr = dict(pages.header)
        hdr["encoding"] = "zstd"
        hdr["compressed_size"] = len(blob)
        m = BlockMeta(tenant_id=tenants[t], encoding="zstd", block_id=ids[i],
                      start_time=hdr["min_start_s"],
                      end_time=hdr["max_end_s"])
        m.search_pages = hdr["n_pages"]
        m.search_size = len(blob)
        m.search_entries_per_page = hdr["entries_per_page"]
        m.search_kv_per_entry = hdr["kv_per_entry"]
        m.total_objects = hdr["n_entries"]
        be.write(tenants[t], m.block_id, NAME_SEARCH, blob)
        be.write(tenants[t], m.block_id, NAME_SEARCH_HEADER,
                 json.dumps(hdr).encode())
        be.write_block_meta(m)
        return len(blob), hdr["kv_per_entry"]

    written = list(pool.map(one, range(n_blocks)))
    groups = [g for size in sizes for g in group_sizes(size, n_pages)]
    return {
        "tenant": tenants[0], "tenants": tenants,
        "blocks": dict(zip(tenants, sizes)), "block_ids": ids,
        "tenant_law": ob._zipf(len(tenants), s).tolist(),
        "tenant_slice": {t: (int(first[r]), int(first[r + 1]))
                         for r, t in enumerate(tenants)},
        "tenant_class": {t: last_group_bucket(size, n_pages)
                         for t, size in zip(tenants, sizes)},
        "group_blocks": groups, "pages_per_block": n_pages,
        "entries": n_blocks * n, "pages": n_blocks * n_pages,
        "kv_per_entry": max(c for _, c in written),
        "disk_bytes": sum(b for b, _ in written), "table": table,
        "key_names": ob.KEY_NAMES,
        "vals": vals_all, "start": start_all, "end": end_all,
        "dur": dur_all, "present": present_all,
        "key_present": key_present_all,
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
