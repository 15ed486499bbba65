"""What one `?agg=red` launch costs the device, by fused members: the
table `chipbench/configs/tempo-search-red16.json` was sized from, and
where the next `perf_opt` on `red16.dashboard` starts.

    chiprun --timeout 1500 -- python3 scripts/red_bench.py \
        --blocks 64 --members 1,2,4,8,16 --calls 3

It builds that many of the cell's blocks
(`chipbench/generators/otel_red.py`, the configuration's corpus
parameters, 65,536 entries a block), stages them as ONE group on a
`MultiBlockEngine` (no server, no batcher: the launch alone), builds the
group's key column (`analytics.build_agg_stage`, timed: `stage` line),
compiles the traffic mix's six templates against the group and launches
them `--calls` times, fenced: solo (Q = 1, each template) and fused
(Q = 2, 4, 8, 16: the templates in turn, as a burst of all together
fuses them), each WITH the `agg` stage and WITHOUT it (the same queries
as plain searches), so the difference is the reduction's. One JSON line
a (Q, agg): the median launch, the first (compile + launch), the
matches; then, for Q = 1 and the largest Q, the device ops of one
profiled launch with the `agg` stage (`ops` lines: the ten that took
most device time). The served path fuses at most
`search_coalesce_max_queries` (8) members. Times are the host's clock
around a fenced launch; on anything but a TPU the lines say so in
`platform` and mean nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", type=int, default=64)
    ap.add_argument("--entries", type=int, default=65536)
    ap.add_argument("--members", default="1,2,4,8,16")
    ap.add_argument("--calls", type=int, default=3)
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--out", default="chiprun_out/red_bench.jsonl")
    args = ap.parse_args()

    import jax

    from chipbench import xplane
    from chipbench.generators import otel_blocks as ob
    from chipbench.generators import otel_red as gen
    from chipbench.run import build_requests
    from tempo_tpu import tempopb
    from tempo_tpu.search.analytics import build_agg_stage
    from tempo_tpu.search.engine import resolve_top_k
    from tempo_tpu.search.multiblock import (
        MultiBlockEngine, compile_multi, stack_queries,
    )
    from tempo_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    tag = {"platform": dev.platform, "kind": dev.device_kind}
    with open(os.path.join(
            ROOT, "chipbench/configs/tempo-search-red16.json")) as f:
        params = dict(json.load(f)["corpus"], config_name="bench",
                      entries_per_block=args.entries, blocks=args.blocks)
    with open(os.path.join(ROOT, "chipbench/traffic/dashboard.json")) as f:
        traffic = json.load(f)
    for op in traffic["ops"]:
        op["variants"] = 1
    os.makedirs(os.path.dirname(os.path.join(ROOT, args.out)), exist_ok=True)
    out = open(os.path.join(ROOT, args.out), "a")

    def say(row: dict) -> None:
        line = json.dumps(dict(tag, **row))
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    vocab, table, gid = gen.prepare(params)
    blocks = [gen.pack_block(*gen.make_block(params, vocab, gid, args.seed, i),
                             table, i)[0] for i in range(args.blocks)]
    # what the op's value draws read of a manifest
    manifest = {
        "tenant": "bench", "time_base": params["time_base"],
        "time_span_s": params["time_span_s"],
        "vocab": {"domains": {k: (v, None if p is None else p.tolist())
                              for k, (v, p) in vocab["domains"].items()}},
        "dur_ms_quantile": lambda q: ob.duration_ms_quantile(
            params, float(q))}
    requests, _ = build_requests(traffic, manifest, args.seed)

    eng = MultiBlockEngine()
    t = time.perf_counter()
    host = eng.stage_host(blocks)
    stack_s = time.perf_counter() - t
    t = time.perf_counter()
    batch = eng.place(host)
    put_s = time.perf_counter() - t
    t = time.perf_counter()
    stage = build_agg_stage(blocks, int(batch.device["entry_valid"].shape[0]),
                            ob.PAGE_ENTRIES)
    build_s = time.perf_counter() - t
    key_bytes = int(stage.host.nbytes)
    t = time.perf_counter()
    jax.block_until_ready(stage.device())
    say({"blocks": args.blocks, "label": "stage", "stack_s": stack_s,
         "put_s": put_s, "agg_build_s": build_s,
         "agg_put_s": time.perf_counter() - t, "agg_key_bytes": key_bytes,
         "agg_keys": stage.n_keys, "services": len(stage.services),
         "pages": int(batch.device["entry_valid"].shape[0]),
         "device_bytes": batch.device_nbytes})

    def query(r: dict, agg: bool):
        q = r["ref"]
        req = tempopb.SearchRequest()
        for k, v in q["tags"].items():
            req.tags[k] = v
        req.limit = q["limit"]
        if q.get("min_ms"):
            req.min_duration_ms = q["min_ms"]
        if q.get("start"):
            req.start, req.end = q["start"], q["end"]
        mq = compile_multi(blocks, req, cache_on=batch)
        if agg:
            mq.agg_stage = stage
        return mq

    def launch(mqs: list):
        """One fenced launch of `mqs`: solo, or fused as the coalescer
        fuses a window's members."""
        if len(mqs) == 1:
            return eng.scan(batch, mqs[0])
        cq = stack_queries(mqs)
        k = max(resolve_top_k(eng.top_k, m.limit) for m in mqs)
        return eng.fetch(eng.coalesced_scan_async(batch, cq, k), cq)

    def ops_of(mqs: list) -> list:
        trace_dir = tempfile.mkdtemp(prefix="red-bench-")
        jax.profiler.start_trace(trace_dir)
        launch(mqs)
        jax.profiler.stop_trace()
        path = xplane.find_trace(trace_dir)
        if path is None:
            return []
        red = xplane.reduce(xplane.load(path))
        return [[n, ns / 1e6] for n, ns in red["ops_ns"][:10]]

    members = [int(x) for x in args.members.split(",")]
    for q in members:
        sets = ([[r] for r in requests] if q == 1 else
                [[requests[j % len(requests)] for j in range(q)]])
        for agg in (True, False):
            for rs in sets:
                mqs = [query(r, agg) for r in rs]
                t = time.perf_counter()
                res = launch(mqs)
                first = time.perf_counter() - t
                ms = []
                for _ in range(args.calls):
                    t = time.perf_counter()
                    res = launch(mqs)
                    ms.append((time.perf_counter() - t) * 1e3)
                count = res[0]
                say({"blocks": args.blocks, "members": q, "agg": agg,
                     "label": rs[0]["name"] if q == 1 else "all-together",
                     "launch_ms": statistics.median(ms), "min_ms": min(ms),
                     "max_ms": max(ms), "first_s": first,
                     "matches": (int(count) if q == 1
                                 else [int(c) for c in count])})
        if q in (1, max(members)):
            say({"blocks": args.blocks, "members": q, "agg": True,
                 "label": "ops", "ops_ms": ops_of(
                     [query(r, True) for r in sets[0]])})
    return 0 if dev.platform == "tpu" else 3


if __name__ == "__main__":
    sys.exit(main())
