"""Offline block tooling: ``python -m tempo_tpu.cli.blocks <cmd>``.

Role-equivalent to cmd/tempo-cli (main.go:38-72): list/view blocks and
indexes, regenerate index/bloom from block data, search backend blocks
directly (the CPU-baseline harness role), and query a running server's
HTTP API.
"""

from __future__ import annotations

import argparse
import json
import sys

from tempo_tpu.backend import LocalBackend, BlockMeta, bloom_name, NAME_DATA, NAME_INDEX
from tempo_tpu.encoding.v2 import (
    BackendBlock,
    IndexReader,
    IndexWriter,
    Record,
    ShardedBloom,
    decompress,
)
from tempo_tpu.encoding.v2.objects import unmarshal_objects
from tempo_tpu.utils.ids import hex_to_trace_id


def cmd_list_blocks(be, args):
    rows = []
    for bid in be.list_blocks(args.tenant):
        try:
            m = be.read_block_meta(args.tenant, bid)
            rows.append({"id": bid, "objects": m.total_objects,
                         "size": m.size, "level": m.compaction_level,
                         "start": m.start_time, "end": m.end_time})
        except Exception:
            try:
                cm = be.read_compacted_meta(args.tenant, bid)
                rows.append({"id": bid, "compacted_at": cm.compacted_time})
            except Exception:
                rows.append({"id": bid, "state": "torn"})
    print(json.dumps(rows, indent=2))


def cmd_view_block(be, args):
    m = be.read_block_meta(args.tenant, args.block)
    out = json.loads(m.to_json())
    idx = IndexReader(be.read(args.tenant, args.block, NAME_INDEX))
    out["index_records"] = len(idx)
    out["pages"] = [
        {"max_id": bytes(idx.ids[i]).hex(), "start": int(idx.starts[i]),
         "len": int(idx.lengths[i])}
        for i in range(min(len(idx), args.limit))
    ]
    print(json.dumps(out, indent=2))


def cmd_find(be, args):
    m = be.read_block_meta(args.tenant, args.block)
    obj = BackendBlock(be, m).find_by_id(hex_to_trace_id(args.trace_id))
    if obj is None:
        print("not found", file=sys.stderr)
        return 1
    from tempo_tpu.model import codec_for

    tr = codec_for(m.data_encoding).prepare_for_read(obj)
    from google.protobuf import json_format

    print(json_format.MessageToJson(tr))
    return 0


def cmd_gen_index(be, args):
    """Rebuild the index from block data (disaster recovery)."""
    m = be.read_block_meta(args.tenant, args.block)
    data = be.read(args.tenant, args.block, NAME_DATA)
    idx = IndexReader(be.read(args.tenant, args.block, NAME_INDEX))
    records = []
    for i in range(len(idx)):
        page = decompress(
            data[int(idx.starts[i]): int(idx.starts[i]) + int(idx.lengths[i])],
            m.encoding,
        )
        last = None
        for oid, _ in unmarshal_objects(page):
            last = oid
        if last is not None:
            records.append(Record(last, int(idx.starts[i]), int(idx.lengths[i])))
    be.write(args.tenant, args.block, NAME_INDEX,
             IndexWriter(m.index_page_size or 1024).write(records))
    print(f"rebuilt index: {len(records)} records")


def cmd_gen_bloom(be, args):
    """Rebuild bloom shards from block data."""
    m = be.read_block_meta(args.tenant, args.block)
    bb = BackendBlock(be, m)
    ids = [oid for oid, _ in bb.iter_objects()]
    shards = max(1, m.bloom_shard_count or 1)
    bloom = ShardedBloom(shards, expected_per_shard=max(1, len(ids) // shards))
    bloom.add_many(ids)
    for s in range(bloom.shard_count):
        be.write(args.tenant, args.block, bloom_name(s), bloom.marshal_shard(s))
    print(f"rebuilt {bloom.shard_count} bloom shards over {len(ids)} ids")


def cmd_search(be, args):
    """Search backend blocks directly (no server) — the offline harness."""
    from tempo_tpu import tempopb
    from tempo_tpu.search import SearchResults
    from tempo_tpu.search.backend_search_block import BackendSearchBlock
    from tempo_tpu.search.batcher import BlockBatcher

    from tempo_tpu.api.params import _duration_ms

    req = tempopb.SearchRequest()
    for pair in args.tags or []:
        k, _, v = pair.partition("=")
        req.tags[k] = v
    req.limit = args.limit
    if args.min_duration:
        req.min_duration_ms = _duration_ms(args.min_duration)
    if args.max_duration:
        req.max_duration_ms = _duration_ms(args.max_duration)
    req.start = args.start
    req.end = args.end
    # every block a whole-container ScanJob, searched through the
    # batcher as the server does (TempoDB.search_block): its breaker
    # gate, host fallback and early quit at the limit
    jobs = []
    for bid in be.list_blocks(args.tenant):
        try:
            m = be.read_block_meta(args.tenant, bid)
        except Exception:
            continue
        jobs.append(BackendSearchBlock(be, m).scan_job())
    results = BlockBatcher().search(jobs, req,
                                    SearchResults(limit=args.limit))
    resp = results.response()
    from google.protobuf import json_format

    print(json_format.MessageToJson(resp))


def cmd_import_ref(be, args) -> int:
    """Import a Go-written v2 block directory into this backend
    (db/importer.py — VERDICT r4 #5 migration path)."""
    import tempfile

    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.db.importer import dir_reader, import_reference_block

    with tempfile.TemporaryDirectory() as wal:
        db = TempoDB(be, wal, TempoDBConfig(host_state_dir=""))
        meta = import_reference_block(dir_reader(args.src_dir), db,
                                      args.tenant)
    print(json.dumps({"imported_block": meta.block_id,
                      "objects": meta.total_objects}))
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser("tempo-tpu-cli")
    p.add_argument("--backend-path", required=True)
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("list-blocks")
    sp.add_argument("tenant")
    sp = sub.add_parser("view-block")
    sp.add_argument("tenant")
    sp.add_argument("block")
    sp.add_argument("--limit", type=int, default=10)
    sp = sub.add_parser("find")
    sp.add_argument("tenant")
    sp.add_argument("block")
    sp.add_argument("trace_id")
    sp = sub.add_parser("gen-index")
    sp.add_argument("tenant")
    sp.add_argument("block")
    sp = sub.add_parser("gen-bloom")
    sp.add_argument("tenant")
    sp.add_argument("block")
    sp = sub.add_parser("import-ref",
                        help="one-way import of a reference-format v2 "
                             "block directory (meta.json + data + index)")
    sp.add_argument("tenant")
    sp.add_argument("src_dir")
    sp = sub.add_parser("search")
    sp.add_argument("tenant")
    sp.add_argument("--tags", nargs="*")
    sp.add_argument("--limit", type=int, default=20)
    sp.add_argument("--min-duration", default="",
                    help="e.g. 100ms, 1.5s (api/params duration syntax)")
    sp.add_argument("--max-duration", default="")
    sp.add_argument("--start", type=int, default=0, help="unix seconds")
    sp.add_argument("--end", type=int, default=0)

    args = p.parse_args(argv)
    be = LocalBackend(args.backend_path)
    fn = {
        "list-blocks": cmd_list_blocks, "view-block": cmd_view_block,
        "find": cmd_find, "gen-index": cmd_gen_index,
        "gen-bloom": cmd_gen_bloom, "search": cmd_search,
        "import-ref": cmd_import_ref,
    }[args.cmd]
    return fn(be, args) or 0


if __name__ == "__main__":
    import signal

    # behave like a unix tool when piped into head etc.
    signal.signal(signal.SIGPIPE, signal.SIG_DFL)
    raise SystemExit(main())
