"""What one structural launch costs the device, by plan and by group size:
the table `chipbench/configs/tempo-search-calltree16.json` was sized
from.

    chiprun --timeout 1500 -- python3 scripts/structural_bench.py \
        --blocks 8,64 --calls 3

For each size it builds that many of the cell's blocks
(`chipbench/generators/otel_calltree.py`, the configuration's corpus
parameters, 65,536 traces a block), stages them as ONE group on a
`MultiBlockEngine` (no server, no batcher: the launch alone), compiles
each of the traffic mix's five templates against it and launches it
`--calls` times, fenced. One JSON line a (size, plan): the median
launch, the first (compile + launch), the span axis, the running-max
passes of its joins by ancestor; and one `stage` line a size: the host's
stacking of the group (`stack_s`) and, of it, the laying out of every
trace's spans depth first (`order_s`, with the rows it moved), the span
axis, its tile (`structural.SPAN_TILE`) and the pad rows the tile's
alignment and the power of two cost. Times are the host's clock around a
fenced launch; on anything but a TPU the lines say so in `platform` and
mean nothing.

`--span-tiles 128,256,512,1024` stages and times every size once a
tile (it sets the module's constant for the run: how the shipped one
was chosen, PERF.md section 6, PR 45).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="8,64")
    ap.add_argument("--entries", type=int, default=65536)
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--out", default="chiprun_out/structural_bench.jsonl")
    ap.add_argument("--span-tiles", default="",
                    help="tiles of the span axis to try, each in turn "
                         "(default: the shipped structural.SPAN_TILE)")
    args = ap.parse_args()

    import jax

    from chipbench.generators import otel_blocks as ob
    from chipbench.generators import otel_calltree as oc
    from chipbench.run import build_requests
    from tempo_tpu import tempopb
    from tempo_tpu.observability import metrics as obs
    from tempo_tpu.search import ir, structural
    from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi
    from tempo_tpu.search.pipeline import EXHAUSTIVE_SEARCH_TAG
    from tempo_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()
    structural.configure(enabled=True)
    dev = jax.devices()[0]
    tag = {"platform": dev.platform, "kind": dev.device_kind}
    with open(os.path.join(
            ROOT, "chipbench/configs/tempo-search-calltree16.json")) as f:
        corpus = dict(json.load(f)["corpus"], config_name="bench",
                      entries_per_block=args.entries)
    with open(os.path.join(ROOT, "chipbench/traffic/structural.json")) as f:
        traffic = json.load(f)
    for op in traffic["ops"]:
        op["variants"] = 1
    vocab, table, gid, ids, params = oc.prepare(corpus)
    os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
    out = open(os.path.join(ROOT, args.out), "a")

    def say(row: dict) -> None:
        line = json.dumps(dict(tag, **row))
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    def bench_group(blocks: list, requests: list, n_blocks: int) -> None:
        """Stage `blocks` as one group, then compile and launch each
        request's plan against it."""
        eng = MultiBlockEngine()
        moved = obs.structural_span_reorder_rows.value(moved="yes")
        order_s = obs.structural_span_order_seconds.value()
        t = time.perf_counter()
        host = eng.stage_host(blocks)
        stack_s = time.perf_counter() - t
        moved = obs.structural_span_reorder_rows.value(moved="yes") - moved
        order_s = obs.structural_span_order_seconds.value() - order_s
        t = time.perf_counter()
        batch = eng.place(host)
        live = sum(b.n_spans for b in blocks)
        rows = int(batch.span_device["span_trace"].shape[0])
        tile = rows // int(batch.span_device["span_tile_block"].shape[0])
        say({"blocks": n_blocks, "label": "stage", "stack_s": stack_s,
             "order_s": order_s, "moved_rows": int(moved),
             "put_s": time.perf_counter() - t, "span_rows": rows,
             "live_rows": live, "span_tile": tile,
             # pad rows: up to each block's next tile, then the axis'
             # power of two
             "align_pad_rows": sum(-b.n_spans % tile for b in blocks),
             "span_bytes": structural.span_device_bytes(batch.span_device),
             "device_bytes": batch.device_nbytes})
        req = tempopb.SearchRequest()
        req.tags[EXHAUSTIVE_SEARCH_TAG] = "1"
        req.limit = 20
        for r in requests:
            expr = ir.parse(json.dumps(r["ref"]["q"]))
            mq = compile_multi(blocks, req, cache_on=batch)
            mq.structural = structural.compile_structural(
                expr, blocks, cache_on=batch,
                staged_dicts=batch.staged_dicts,
                entry_kv_slots=blocks[0].geometry.kv_per_entry)
            rel, scans = structural.plan_joins(mq.structural.plan)
            t = time.perf_counter()
            res = eng.scan(batch, mq)
            first = time.perf_counter() - t
            ms = []
            for _ in range(args.calls):
                t = time.perf_counter()
                res = eng.scan(batch, mq)
                ms.append((time.perf_counter() - t) * 1e3)
            say({"blocks": n_blocks, "label": r["name"], "rel": rel,
                 "span_rows": rows, "span_tile": tile, "join_scans": scans,
                 "launch_ms": statistics.median(ms),
                 "min_ms": min(ms), "max_ms": max(ms),
                 "first_s": first, "matches": int(res[0]),
                 "inspected": int(res[1])})

    pages, spans_all, vals_all, dur_all = [], [], [], []
    for n_blocks in [int(x) for x in args.blocks.split(",")]:
        params["blocks"] = n_blocks      # the block's hour of the day
        while len(pages) < n_blocks:
            i = len(pages)
            vals, start, end, dur = ob.make_block(params, vocab, gid,
                                                  args.seed, i)
            spans = oc.make_spans(params, ids, vals, dur, args.seed, i)
            pages.append(oc.pack_block(vals, start, end, dur, spans, table,
                                       i)[0])
            spans_all.append(spans)
            vals_all.append(vals.T)
            dur_all.append(dur)
        blocks = pages[:n_blocks]
        # what the op's value draws read of a manifest
        manifest = {
            "tenant": "bench", "table": table, "key_names": ob.KEY_NAMES,
            "span_key_names": oc.SPAN_KEYS,
            "vals": vals_all[:n_blocks], "dur": dur_all[:n_blocks],
            "span_count": [s["count"] for s in spans_all[:n_blocks]],
            "span_parent": [s["parent"] for s in spans_all[:n_blocks]],
            "span_dur": [s["dur"] for s in spans_all[:n_blocks]],
            "span_kind": [s["kind"] for s in spans_all[:n_blocks]],
            "span_vals": [s["vals"] for s in spans_all[:n_blocks]],
            "call_edges": [(oc.ROLE_OF[a], oc.ROLE_OF[b])
                           for a, b in oc.call_edges()],
            "span_services": [s for s in vocab["services"]
                              if s.split("-", 1)[1] in oc.ROLE_OF.values()],
            "vocab": {"services": vocab["services"],
                      "teams": list(ob.TEAMS), "roles": list(ob.ROLES),
                      "domains": {k: (v, None if p is None else p.tolist())
                                  for k, (v, p) in vocab["domains"].items()}},
            "dur_ms_quantile": lambda q: ob.duration_ms_quantile(
                params, float(q)),
        }
        requests, _ = build_requests(traffic, manifest, args.seed)
        for tile in [int(t) for t in args.span_tiles.split(",") if t] \
                or [structural.SPAN_TILE]:
            structural.SPAN_TILE = tile
            bench_group(blocks, requests, n_blocks)
    return 0 if dev.platform == "tpu" else 3


if __name__ == "__main__":
    sys.exit(main())
