"""North-star benchmark: columnar tag-scan throughput, TPU vs CPU.

Mirrors the reference's backend-search bench harness
(tempodb/search/backend_search_block_test.go:128-172, which prints MiB/s
and Mtraces/s for the FlatBuffer page scan): same corpus, same query, two
executions —

  - CPU baseline: vectorized numpy implementation of the identical
    predicate (isin membership + bincount segment-OR + filters) — a fair
    stand-in for the reference's Go columnar scan loop.
  - TPU engine: the jit scan kernel (tempo_tpu.search.engine), staged
    arrays resident in HBM, timed over repeated queries.

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "traces/s", "vs_baseline": N}
vs_baseline = TPU rate / CPU rate (target: ≥10, BASELINE.json).

Hang-proof harness: `python bench.py` runs a stdlib-only ORCHESTRATOR
that never touches jax itself, so the chip belongs to one phase child
at a time. Each bench config runs as `python bench.py --phase NAME` in
its own subprocess with its own deadline, checkpointing its result to
BENCH_CKPT_DIR as it completes; the final line assembles whatever
finished, with explicit per-phase errors for anything that hung. A
preflight device probe runs first (BENCH_PREFLIGHT_ATTEMPTS, default 1;
BENCH_TIMEOUT_PROBE seconds per attempt): if the device does not answer
the run ends non-zero with nothing measured. There is no CPU stand-in:
a run whose probe reports platform `cpu` is a dry run of the harness —
headline zeroed, exit code 4 — and a failed phase makes the exit code
non-zero whatever else succeeded.
A hung phase loses only itself — never the completed phases.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import time

import numpy as np

import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)


def build_corpus(n_entries: int, E: int = 1024, C: int = 4, seed: int = 7):
    """Synthesize ColumnarPages-shaped arrays directly (fast, numpy) —
    semantically identical to ColumnarPages.build output."""
    from tempo_tpu.search.columnar import ColumnarPages, PageGeometry

    rng = np.random.default_rng(seed)
    services = [f"svc-{i:03d}" for i in range(64)]
    statuses = ["200", "404", "500"]
    regions = ["us-east-1", "us-west-2", "eu-west-1", "ap-south-1"]
    names = [f"op-{i}" for i in range(32)]
    key_dict = sorted(["service.name", "http.status_code", "region", "name"])
    val_dict = sorted(set(services + statuses + regions + names))
    vidx = {v: i for i, v in enumerate(val_dict)}
    kidx = {k: i for i, k in enumerate(key_dict)}

    P = -(-n_entries // E)
    assert C >= 4

    svc = rng.integers(0, len(services), size=(P, E))
    st = rng.integers(0, len(statuses), size=(P, E))
    rg = rng.integers(0, len(regions), size=(P, E))
    nm = rng.integers(0, len(names), size=(P, E))
    svc_ids = np.array([vidx[s] for s in services], dtype=np.int32)[svc]
    st_ids = np.array([vidx[s] for s in statuses], dtype=np.int32)[st]
    rg_ids = np.array([vidx[s] for s in regions], dtype=np.int32)[rg]
    nm_ids = np.array([vidx[s] for s in names], dtype=np.int32)[nm]

    kv_key = np.full((P, E, C), -1, dtype=np.int32)
    kv_val = np.full((P, E, C), -1, dtype=np.int32)
    for j, (kname, vals) in enumerate((
        ("service.name", svc_ids), ("http.status_code", st_ids),
        ("region", rg_ids), ("name", nm_ids),
    )):
        kv_key[:, :, j] = kidx[kname]
        kv_val[:, :, j] = vals

    e_idx = np.arange(E, dtype=np.int32)
    entry_start = (1_600_000_000 + rng.integers(0, 86_400, size=(P, E))).astype(np.uint32)
    entry_end = entry_start + rng.integers(0, 60, size=(P, E)).astype(np.uint32)
    entry_dur = rng.integers(1, 60_000, size=(P, E)).astype(np.uint32)
    entry_valid = np.zeros((P, E), dtype=bool)
    flat_n = np.minimum(n_entries - np.arange(P) * E, E)
    entry_valid[:] = e_idx[None, :] < flat_n[:, None]

    pages = ColumnarPages(
        geometry=PageGeometry(E, C), key_dict=key_dict, val_dict=val_dict,
        kv_key=kv_key, kv_val=kv_val,
        entry_start=entry_start, entry_end=entry_end, entry_dur=entry_dur,
        entry_valid=entry_valid,
        entry_root_svc=svc_ids.astype(np.int32),
        entry_root_name=nm_ids.astype(np.int32),
        trace_ids=np.zeros((P, E, 16), dtype=np.uint8),
        n_entries=n_entries,
        header={"n_entries": n_entries, "n_pages": P, "entries_per_page": E,
                "kv_per_entry": C},
    )
    return pages


def _dispatch_count() -> float:
    """Total device kernel dispatches so far (batched serving path)."""
    from tempo_tpu.observability import metrics as obs

    return obs.scan_dispatches.value(mode="batched")


def cpu_scan(pages, cq):
    """Vectorized numpy reference scan — the CPU baseline. Same dense
    layout, same bitmap membership test as the device kernel."""
    kv_key, kv_val = pages.kv_key, pages.kv_val
    mask = pages.entry_valid.copy()
    for t in range(cq.n_terms):
        k = cq.term_keys[t]
        vals = cq.term_vals[t]
        vals = vals[vals != np.int32(2**31 - 1)]
        valm = np.isin(kv_val, vals)
        mask &= ((kv_key == k) & valm).any(axis=-1)
    mask &= (pages.entry_dur >= cq.dur_lo) & (pages.entry_dur <= cq.dur_hi)
    mask &= (pages.entry_end >= cq.win_start) & (pages.entry_start <= cq.win_end)
    return int(mask.sum())


def _timed_rate(enqueue_fn, n_entries, iters):
    """Entries per second of the kernel alone. Dispatch is asynchronous
    and device execution is in-order, so enqueue `iters` kernels and
    wait for the last with block_until_ready; the batch grows until the
    window is long enough to resolve (callers have already compiled and
    warmed the kernel)."""
    import jax

    while True:
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = enqueue_fn()
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        if dt > 0.05 or iters >= 4096:
            break
        iters *= 4
    return n_entries * iters / max(dt, 1e-9)


def bench_single_block(n_entries, iters):
    """Config 1+3: single corpus, 2-term AND + duration (the headline)."""
    from tempo_tpu import tempopb
    from tempo_tpu.search.engine import ScanEngine, stage
    from tempo_tpu.search.pipeline import compile_query

    pages = build_corpus(n_entries)
    req = tempopb.SearchRequest()
    req.tags["service.name"] = "svc-007"
    req.tags["http.status_code"] = "500"
    req.min_duration_ms = 500
    req.limit = 20
    cq = compile_query(pages.key_dict, pages.val_dict, req)
    assert cq is not None, "bench query pruned the corpus block"

    cpu_count = cpu_scan(pages, cq)
    t0 = time.perf_counter()
    cpu_iters = max(1, min(3, iters))
    for _ in range(cpu_iters):
        cpu_scan(pages, cq)
    cpu_rate = n_entries * cpu_iters / (time.perf_counter() - t0)

    eng = ScanEngine(top_k=128)
    sp = stage(pages)
    count, _, _, _ = eng.scan_staged(sp, cq)  # compile+warm
    assert count == cpu_count, f"device {count} != cpu {cpu_count}"
    tpu_rate = _timed_rate(lambda: eng.scan_staged_async(sp, cq),
                           n_entries, iters)

    # duration-only filter (config 3) on the same staged corpus
    dreq = tempopb.SearchRequest()
    dreq.min_duration_ms = 30_000
    dreq.limit = 20
    dcq = compile_query(pages.key_dict, pages.val_dict, dreq)
    eng.scan_staged(sp, dcq)
    dur_rate = _timed_rate(lambda: eng.scan_staged_async(sp, dcq),
                           n_entries, iters)
    return tpu_rate, cpu_rate, int(count), dur_rate


def bench_multiblock(n_blocks, entries_per_block, iters):
    """Config 2: many blocks batched into one kernel call."""
    from tempo_tpu import tempopb
    from tempo_tpu.search.multiblock import (
        MultiBlockEngine, compile_multi, stack_blocks,
    )

    blocks = [build_corpus(entries_per_block, seed=s) for s in range(n_blocks)]
    req = tempopb.SearchRequest()
    req.tags["service.name"] = "svc-007"
    req.tags["http.status_code"] = "500"
    req.limit = 20
    mq = compile_multi(blocks, req)
    assert mq is not None, "bench query pruned every block"
    batch = stack_blocks(blocks)
    eng = MultiBlockEngine(top_k=128)
    count, inspected, _, _ = eng.scan(batch, mq)
    total = n_blocks * entries_per_block
    assert inspected == total
    rate = _timed_rate(lambda: eng.scan_async(batch, mq), total, iters)
    return rate, int(count)


def bench_serving(n_blocks, entries_per_block, iters):
    """Config 2 through the SERVING path: the same multi-block corpus
    written as real backend search blocks and queried via TempoDB.search —
    the production entry (frontend → querier → TempoDB), so the number
    includes per-query host compile, batch-cache lookup, kernel dispatch
    and result fetch. Also reports p50/p95 serving latency."""
    import json as _json
    import tempfile

    from tempo_tpu import tempopb
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.backend.types import (
        BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER,
    )
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.encoding.v2.compression import compress

    total = n_blocks * entries_per_block
    with tempfile.TemporaryDirectory() as td:
        be = LocalBackend(td + "/blocks")
        db = TempoDB(be, td + "/wal", TempoDBConfig())
        metas = []
        for s in range(n_blocks):
            pages = build_corpus(entries_per_block, seed=s)
            m = BlockMeta(tenant_id="bench", encoding="zstd")
            blob = compress(pages.to_bytes(), "zstd")
            hdr = dict(pages.header)
            hdr["encoding"] = "zstd"
            hdr["compressed_size"] = len(blob)
            be.write("bench", m.block_id, NAME_SEARCH, blob)
            be.write("bench", m.block_id, NAME_SEARCH_HEADER,
                     _json.dumps(hdr).encode())
            metas.append(m)
        db.blocklist.update("bench", add=metas)

        req = tempopb.SearchRequest()
        req.tags["service.name"] = "svc-007"
        req.tags["http.status_code"] = "500"
        req.limit = 20
        r = db.search("bench", req)  # warm: stage + compile
        assert r.metrics.inspected_traces == total, (
            r.metrics.inspected_traces, total)
        dispatches = db.batcher.last_dispatches

        lat = []
        for _ in range(max(3, iters)):
            t0 = time.perf_counter()
            db.search("bench", req)
            lat.append(time.perf_counter() - t0)
        lat.sort()
        p50 = lat[len(lat) // 2] * 1e3
        p95 = lat[min(len(lat) - 1, int(len(lat) * 0.95))] * 1e3
        rate = total / (sum(lat) / len(lat))
        return rate, p50, p95, dispatches


def bench_coalesced_serving(n_blocks, entries_per_block, iters,
                            concurrency=8):
    """Cross-request query coalescing through the serving path: N
    concurrent synthetic tenants issue DISTINCT predicates over the same
    device-resident block cache; dispatches landing on the same staged
    batch within the coalescing window fuse into one multi-query kernel
    launch (search/batcher.QueryCoalescer). Reports dispatches-per-
    request (target ≤ 1/2 at concurrency 8 — the whole point), the
    coalesce ratio (queries per fused launch), p50/p95 per-request
    latency, and the HBM batch-cache hit counters.

    NOTE scan_dispatches semantics: with coalescing active the counter's
    mode="batched" series counts SOLO kernel launches and
    mode="coalesced" counts fused multi-query launches — a fused launch
    increments once however many requests it served. Phases that predate
    coalescing read mode="batched" only and keep their old meaning
    (serial runs flush solo)."""
    import json as _json
    import tempfile
    import threading

    from tempo_tpu import tempopb
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.backend.types import (
        BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER,
    )
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.encoding.v2.compression import compress
    from tempo_tpu.observability import metrics as obs

    total = n_blocks * entries_per_block
    with tempfile.TemporaryDirectory() as td:
        be = LocalBackend(td + "/blocks")
        db = TempoDB(be, td + "/wal", TempoDBConfig(
            # a slightly wider window than the serving default: the bench
            # models synchronized dashboard fan-out, and stragglers need
            # the headroom
            search_coalesce_window_s=0.01,
            search_coalesce_max_queries=concurrency))
        metas = []
        for s in range(n_blocks):
            pages = build_corpus(entries_per_block, seed=s)
            m = BlockMeta(tenant_id="bench", encoding="none")
            blob = compress(pages.to_bytes(), "none")
            hdr = dict(pages.header)
            hdr["encoding"] = "none"
            hdr["compressed_size"] = len(blob)
            be.write("bench", m.block_id, NAME_SEARCH, blob)
            be.write("bench", m.block_id, NAME_SEARCH_HEADER,
                     _json.dumps(hdr).encode())
            metas.append(m)
        db.blocklist.update("bench", add=metas)

        def mk_req(i):
            req = tempopb.SearchRequest()
            req.tags["service.name"] = f"svc-{i:03d}"
            req.tags["http.status_code"] = "500"
            req.limit = 20
            return req

        # warm: stage to HBM + compile the solo AND fused kernel shapes
        # (the fused shape pads Q to pow2, so one warm fusion covers the
        # steady state); correctness-check against the serial path
        r = db.search("bench", mk_req(0))
        assert r.metrics.inspected_traces == total, (
            r.metrics.inspected_traces, total)
        serial = {}
        for i in range(concurrency):
            serial[i] = db.search(
                "bench", mk_req(i)).response().SerializeToString()

        barrier = threading.Barrier(concurrency)
        rounds = max(3, iters)
        lat: list[float] = []
        lat_lock = threading.Lock()
        mismatches = []

        def worker(wi, n_rounds):
            for _rnd in range(n_rounds):
                barrier.wait()  # synchronized arrival: the dashboard
                # fan-out shape (N panels firing together)
                t0 = time.perf_counter()
                got = db.search(
                    "bench", mk_req(wi)).response().SerializeToString()
                dt = time.perf_counter() - t0
                with lat_lock:
                    lat.append(dt)
                    if got != serial[wi]:
                        mismatches.append(wi)

        def launches():
            return (obs.scan_dispatches.value(mode="batched")
                    + obs.scan_dispatches.value(mode="coalesced"))

        # one synchronized warm round so the fused (Q=concurrency) kernel
        # shape compiles outside the measured window
        warm = [threading.Thread(target=worker, args=(i, 1))
                for i in range(concurrency)]
        for t in warm:
            t.start()
        for t in warm:
            t.join()
        lat.clear()

        d0 = launches()
        q0 = obs.coalesced_queries.value()
        f0 = obs.scan_dispatches.value(mode="coalesced")
        # cache counters are process-lifetime: snapshot so the reported
        # hits/evicts cover the measured rounds only, not the serial
        # correctness pass and warm round
        h0 = obs.batch_cache_events.value(result="hit")
        e0 = obs.batch_cache_events.value(result="evict")
        threads = [threading.Thread(target=worker, args=(i, rounds))
                   for i in range(concurrency)]
        t_run0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        run_s = time.perf_counter() - t_run0
        assert not mismatches, f"coalesced results diverged: {mismatches}"

        n_requests = concurrency * rounds
        dispatches = launches() - d0
        fused = obs.scan_dispatches.value(mode="coalesced") - f0
        fused_queries = obs.coalesced_queries.value() - q0
        lat.sort()
        coalescer = db.batcher.coalescer
        window_ms = (coalescer.stats()["window_ms"]
                     if coalescer is not None else 0.0)
        return {
            "blocks": n_blocks,
            "entries_per_block": entries_per_block,
            "concurrency": concurrency,
            "rounds": rounds,
            "requests": n_requests,
            "scan_dispatches": dispatches,
            "dispatches_per_request": round(dispatches / n_requests, 3),
            "coalesce_ratio": round(fused_queries / fused, 2) if fused else 0,
            "coalesce_window_ms": window_ms,
            "p50_ms": round(lat[len(lat) // 2] * 1e3, 1),
            "p95_ms": round(lat[min(len(lat) - 1,
                                    int(len(lat) * 0.95))] * 1e3, 1),
            "requests_per_sec": round(n_requests / run_s, 1),
            "hbm_cache_hits": obs.batch_cache_events.value(result="hit") - h0,
            "hbm_cache_evicts": (obs.batch_cache_events.value(result="evict")
                                 - e0),
        }


def bench_scale(n_blocks, entries_per_block, iters):
    """North-star-scale serving (BASELINE config 5): a
    10K-block blocklist driven through the production read path, with the
    O(blocks) host costs broken out.

    Scaling law (stated, not hidden): the 1B-span north star is 10K
    blocks x 100K spans; this corpus is 10K blocks x entries_per_block
    (disk/HBM-bounded), which exercises every component whose cost scales
    with BLOCK COUNT at full size — poller, blocklist, frontend job
    sharding, batch grouping, per-block query compile, result merge. The
    per-ENTRY device-scan cost scales with the separately-measured kernel
    rate (configs.multiblock traces_per_sec); full-scale p50 is
    host_ms + 1e9 / (kernel_rate x n_chips).

    Measures via TempoDB.search (querier inner path): cold-tags p50 (new
    tag-set: per-block dictionary compile runs) vs warm p50 (compile
    cache hits) — the difference IS the per-query host compile cost at
    10K blocks; and via the full HTTP->frontend->querier path (job
    sharding + batched SearchBlocksRequests + merge)."""
    import json as _json
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from tempo_tpu import tempopb
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.backend.types import (
        BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER,
    )
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.encoding.v2.compression import compress

    E = min(512, entries_per_block)
    with tempfile.TemporaryDirectory() as td:
        be = LocalBackend(td + "/blocks")

        # 16 distinct containers cycled across the block ids: block-count
        # costs are what's under test; per-block content diversity only
        # needs to defeat trivial dedup
        t0 = time.perf_counter()
        variants = []
        for s in range(16):
            pages = build_corpus(entries_per_block, E=E, seed=100 + s)
            blob = compress(pages.to_bytes(), "zstd")
            hdr = dict(pages.header)
            hdr["encoding"] = "zstd"
            hdr["compressed_size"] = len(blob)
            variants.append((blob, _json.dumps(hdr).encode(), hdr))

        def write_block(i):
            blob, hdr_bytes, hdr = variants[i % len(variants)]
            m = BlockMeta(tenant_id="bench", encoding="zstd")
            m.search_pages = hdr["n_pages"]
            m.search_size = len(blob)
            m.search_entries_per_page = hdr["entries_per_page"]
            m.search_kv_per_entry = hdr["kv_per_entry"]
            m.total_objects = hdr["n_entries"]
            be.write("bench", m.block_id, NAME_SEARCH, blob)
            be.write("bench", m.block_id, NAME_SEARCH_HEADER, hdr_bytes)
            be.write_block_meta(m)

        with ThreadPoolExecutor(16) as ex:
            list(ex.map(write_block, range(n_blocks)))
        build_s = time.perf_counter() - t0

        # host cost 1: poller over a 10K-block bucket.
        # batch cap tuned up for a single-chip 10K-block deployment: with
        # 1-page blocks the whole tenant fits a few dispatches, and each
        # dispatch pays its host sync once
        db = TempoDB(be, td + "/wal",
                     TempoDBConfig(search_max_batch_pages=16384))
        t0 = time.perf_counter()
        db.poll()
        poll_ms = (time.perf_counter() - t0) * 1e3
        n_found = len(db.blocklist.metas("bench"))
        assert n_found == n_blocks, (n_found, n_blocks)

        def mk_req(svc):
            req = tempopb.SearchRequest()
            req.tags["service.name"] = svc
            req.tags["http.status_code"] = "500"
            req.limit = 20
            return req

        total = n_blocks * entries_per_block
        # warm-up: stage all blocks to HBM + compile one tag-set
        t0 = time.perf_counter()
        r = db.search("bench", mk_req("svc-000"))
        first_query_s = time.perf_counter() - t0
        assert r.metrics.inspected_traces == total, (
            r.metrics.inspected_traces, total)
        dispatches = db.batcher.last_dispatches

        def timed(reqs):
            lat = []
            for rq in reqs:
                t0 = time.perf_counter()
                db.search("bench", rq)
                lat.append(time.perf_counter() - t0)
            lat.sort()
            return (lat[len(lat) // 2] * 1e3,
                    lat[min(len(lat) - 1, int(len(lat) * 0.95))] * 1e3)

        n = max(5, iters)
        # warm: same tags every time -> per-block compile cache hits
        warm_p50, warm_p95 = timed([mk_req("svc-001")] * n)
        # cold tags: a NEW tag-set per query -> the per-block dictionary
        # compile runs for all n_blocks on every query
        cold_p50, cold_p95 = timed([mk_req(f"svc-{2 + i:03d}") for i in range(n)])

        # full HTTP -> frontend (job shard + batch) -> querier path
        from tempo_tpu.api.http import HTTPApi
        from tempo_tpu.modules import App, AppConfig

        from tempo_tpu.modules.frontend import FrontendConfig

        app = App(AppConfig(
            backend={"backend": "local", "local": {"path": td + "/blocks"}},
            wal_dir=td + "/wal-app",
            # default auto batch sizing: one batched SearchBlocksRequest
            # per querier -> one kernel dispatch + one device sync per
            # HTTP request
            frontend=FrontendConfig()))
        app.reader_db = db  # share the staged/blocklist state
        for q in app.queriers:
            q.db = db
        app.frontend.db = db
        api = HTTPApi(app)
        # warm the http-path's own group compositions (page-range batches
        # stage separately from the whole-tenant groups above)
        api.handle("GET", "/api/search",
                   {"tags": "service.name=svc-001 http.status_code=500",
                    "limit": "20"}, {"X-Scope-OrgID": "bench"})
        http_lat = []
        d0 = _dispatch_count()
        for i in range(n):
            t0 = time.perf_counter()
            code, doc = api.handle(
                "GET", "/api/search",
                {"tags": "service.name=svc-001 http.status_code=500",
                 "limit": "20"},
                {"X-Scope-OrgID": "bench"})
            http_lat.append(time.perf_counter() - t0)
            assert code == 200, (code, doc)
        http_dispatches_per_req = (_dispatch_count() - d0) / n
        http_lat.sort()
        http_p50 = http_lat[len(http_lat) // 2] * 1e3
        http_p95 = http_lat[min(len(http_lat) - 1,
                                int(len(http_lat) * 0.95))] * 1e3

        return {
            "blocks": n_blocks,
            "entries_per_block": entries_per_block,
            "total_entries": total,
            "corpus_build_s": round(build_s, 1),
            "poll_ms": round(poll_ms, 1),
            "first_query_ms": round(first_query_s * 1e3, 1),
            "scan_dispatches": dispatches,
            "p50_ms": round(warm_p50, 1),
            "p95_ms": round(warm_p95, 1),
            "cold_tags_p50_ms": round(cold_p50, 1),
            "cold_tags_p95_ms": round(cold_p95, 1),
            "host_compile_per_query_ms": round(max(0.0, cold_p50 - warm_p50), 1),
            "distinct_dicts": 16,
            "http_path_p50_ms": round(http_p50, 1),
            "http_path_p95_ms": round(http_p95, 1),
            # the target is ~1 kernel dispatch per HTTP request
            "http_dispatches_per_request": round(http_dispatches_per_req, 2),
        }


def bench_scale_large(n_blocks, entries_per_block, iters):
    """Serving economics at REALISTIC block sizes (>=64K
    entries/block) with the HBM-overflow path exercised honestly.

    Three regimes measured over the same corpus:
      - prewarm: poll + background-prewarm cost (staging + compile warm),
        then the first query (which should pay neither);
      - warm: every group HBM-resident;
      - evicted: HBM budget shrunk below the working set, so every query
        re-stages groups from the host-RAM stacked tier (H2D only, no
        IO/decompress), overlapped with compute by the staging lookahead.
    """
    import json as _json
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from tempo_tpu import tempopb
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.backend.types import (
        BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER,
    )
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.encoding.v2.compression import compress

    E = 1024
    total = n_blocks * entries_per_block
    with tempfile.TemporaryDirectory() as td:
        be = LocalBackend(td + "/blocks")
        t0 = time.perf_counter()
        variants = []
        for s in range(16):
            pages = build_corpus(entries_per_block, E=E, seed=300 + s)
            blob = compress(pages.to_bytes(), "zstd")
            hdr = dict(pages.header)
            hdr["encoding"] = "zstd"
            hdr["compressed_size"] = len(blob)
            variants.append((blob, _json.dumps(hdr).encode(), hdr))

        def write_block(i):
            blob, hdr_bytes, hdr = variants[i % len(variants)]
            m = BlockMeta(tenant_id="bench", encoding="zstd")
            m.search_pages = hdr["n_pages"]
            m.search_size = len(blob)
            m.search_entries_per_page = hdr["entries_per_page"]
            m.search_kv_per_entry = hdr["kv_per_entry"]
            m.total_objects = hdr["n_entries"]
            be.write("bench", m.block_id, NAME_SEARCH, blob)
            be.write("bench", m.block_id, NAME_SEARCH_HEADER, hdr_bytes)
            be.write_block_meta(m)

        with ThreadPoolExecutor(16) as ex:
            list(ex.map(write_block, range(n_blocks)))
        build_s = time.perf_counter() - t0

        # 8192-page groups (~100-200 MB staged): the eviction quantum —
        # a smaller group re-stages faster after an eviction, for a few
        # extra (async-enqueued) dispatches per query
        db = TempoDB(be, td + "/wal", TempoDBConfig(
            search_max_batch_pages=int(os.environ.get(
                "BENCH_LARGE_BATCH_PAGES", 8192)),
            search_batch_cache_bytes=13 << 30,   # v5e HBM is 16 GB
            search_host_cache_bytes=48 << 30,
        ))
        t0 = time.perf_counter()
        db.poll()
        poll_ms = (time.perf_counter() - t0) * 1e3
        assert len(db.blocklist.metas("bench")) == n_blocks

        # prewarm: stage host+HBM and warm the XLA compile cache
        t0 = time.perf_counter()
        db.prewarm(["bench"], background=False)
        prewarm_s = time.perf_counter() - t0

        def mk_req(svc):
            req = tempopb.SearchRequest()
            req.tags["service.name"] = svc
            req.tags["http.status_code"] = "500"
            req.limit = 20
            return req

        t0 = time.perf_counter()
        r = db.search("bench", mk_req("svc-001"))
        first_query_ms = (time.perf_counter() - t0) * 1e3
        assert r.metrics.inspected_traces == total, (
            r.metrics.inspected_traces, total)
        dispatches = db.batcher.last_dispatches

        def timed(reqs):
            lat = []
            for rq in reqs:
                t0 = time.perf_counter()
                db.search("bench", rq)
                lat.append(time.perf_counter() - t0)
            lat.sort()
            return (lat[len(lat) // 2] * 1e3,
                    lat[min(len(lat) - 1, int(len(lat) * 0.95))] * 1e3)

        n = max(3, iters)
        warm_p50, warm_p95 = timed([mk_req("svc-001")] * n)

        # sustained H2D bandwidth of this machine: the evicted numbers
        # below are H2D-bound and must be read against it
        import numpy as np

        import jax
        probe = np.zeros((32 << 20,), dtype=np.int32)  # 128 MB
        jax.device_put(probe).block_until_ready()  # warm the path
        t0 = time.perf_counter()
        jax.device_put(probe).block_until_ready()
        h2d_mbps = 128 / (time.perf_counter() - t0)

        # evicted regime: before each query evict the LRU group from HBM
        # (churn scenario: a poll displaced part of the working set); the
        # query re-stages that group from the host-RAM stacked tier —
        # one H2D copy, no IO/decompress — overlapped by the lookahead
        hbm_bytes = db.batcher._cache_total
        ev_lat = []
        ev_group_mb = 0
        for _ in range(n):
            with db.batcher._lock:
                if len(db.batcher._cache) > 1:
                    _, old = db.batcher._cache.popitem(last=False)
                    db.batcher._cache_total -= old.nbytes
                    ev_group_mb = old.nbytes / (1 << 20)
            t0 = time.perf_counter()
            db.search("bench", mk_req("svc-001"))
            ev_lat.append(time.perf_counter() - t0)
        ev_lat.sort()
        ev_p50 = ev_lat[len(ev_lat) // 2] * 1e3
        ev_p95 = ev_lat[min(len(ev_lat) - 1, int(len(ev_lat) * 0.95))] * 1e3

        return {
            "blocks": n_blocks,
            "entries_per_block": entries_per_block,
            "total_entries": total,
            "corpus_build_s": round(build_s, 1),
            "poll_ms": round(poll_ms, 1),
            "prewarm_s": round(prewarm_s, 1),
            "first_query_after_prewarm_ms": round(first_query_ms, 1),
            "scan_dispatches": dispatches,
            "hbm_working_set_mb": round(hbm_bytes / (1 << 20)),
            "host_tier_mb": round(db.batcher._host_total / (1 << 20)),
            "p50_ms": round(warm_p50, 1),
            "p95_ms": round(warm_p95, 1),
            "evicted_p50_ms": round(ev_p50, 1),
            "evicted_p95_ms": round(ev_p95, 1),
            "evicted_group_mb": round(ev_group_mb),
            "h2d_mbps": round(h2d_mbps),
        }


def bench_high_cardinality(n_entries, cardinality, iters,
                           probe_min_vals=None):
    """Config 4: substring search against a huge value dictionary. Both
    prefilter executions are measured over the same corpus and query:

      - HOST path (`dict_prefilter_ms`): native memmem / numpy scan →
        id ranges → range-compare scan kernel (the pre-PR4 pipeline);
      - DEVICE path (`device_probe_ms`): packed dictionary staged to
        HBM, rolling-window probe kernel → hit mask → mask-lookup scan
        kernel (search/dict_probe.py) — the near-data-processing move.

    Matches must be identical between the paths (asserted), and the
    scan-rate comparison re-validates the mask-lookup-vs-range-compare
    tradeoff (the ids_to_ranges gather measurement) every round instead
    of assuming it."""
    import numpy as np

    from tempo_tpu import tempopb
    from tempo_tpu.search import dict_probe
    from tempo_tpu.search.engine import ScanEngine, stage
    from tempo_tpu.search.pipeline import compile_query, pack_val_dict

    pages = build_corpus(n_entries)
    # swap the region column for a high-cardinality id attribute
    vd = [f"session-{i:08d}" for i in range(cardinality)]
    rng = np.random.default_rng(3)
    hits = rng.integers(0, cardinality, size=pages.kv_val[:, :, 2].shape)
    base = len(pages.val_dict)
    pages.val_dict = pages.val_dict + vd
    pages.kv_val[:, :, 2] = base + hits

    req = tempopb.SearchRequest()
    req.tags["region"] = "session-0000123"  # prefix → 10 matching values
    req.limit = 20
    packed = pack_val_dict(pages.val_dict)
    t0 = time.perf_counter()
    cq = compile_query(pages.key_dict, pages.val_dict, req, packed_vals=packed)
    compile_ms = (time.perf_counter() - t0) * 1e3
    assert cq is not None, (
        "high-cardinality query matched no dictionary values — "
        "BENCH_CARDINALITY must exceed ~1240 so the session prefix exists"
    )
    eng = ScanEngine(top_k=128)
    sp = stage(pages, probe_min_vals=0)  # host-path staging: no dict
    count, _, h_scores, h_idx = eng.scan_staged(sp, cq)
    rate = _timed_rate(lambda: eng.scan_staged_async(sp, cq),
                       n_entries, iters)

    # --- device-resident probe over the same staged pages ---
    probe = {"device_probe_ms": None, "device_probe_rate": None,
             "device_probe_stage_ms": None}
    mv = (dict_probe.DEVICE_PROBE_MIN_VALS if probe_min_vals is None
          else probe_min_vals)
    if 0 < mv <= len(pages.val_dict):
        t0 = time.perf_counter()
        sp.staged_dict = dict_probe.stage_val_dict(pages.val_dict,
                                                   cache_on=pages)
        for a in sp.staged_dict.device.values():
            a.block_until_ready()
        probe["device_probe_stage_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 1)

        def dev_compile():
            # fresh compile each call (no cache_on): probe dispatch +
            # the [T]-bool any_hits prune sync — the replacement for the
            # host prefilter's dict_prefilter_ms
            return compile_query(pages.key_dict, pages.val_dict, req,
                                 staged_dict=sp.staged_dict)

        cq_dev = dev_compile()  # warm: compiles the probe kernel
        t0 = time.perf_counter()
        n_probe = max(3, min(iters, 10))
        for _ in range(n_probe):
            dev_compile()
        probe["device_probe_ms"] = round(
            (time.perf_counter() - t0) / n_probe * 1e3, 1)

        d_count, _, d_scores, d_idx = eng.scan_staged(sp, cq_dev)
        assert int(d_count) == int(count), (
            f"device probe diverged: {int(d_count)} != {int(count)}")
        assert np.array_equal(np.asarray(d_scores), np.asarray(h_scores)), \
            "device-probe top-k scores diverged from host path"
        probe["device_probe_rate"] = round(_timed_rate(
            lambda: eng.scan_staged_async(sp, cq_dev), n_entries, iters))

        # --- offload planner calibration: feed the MEASURED host and
        # device timings from this corpus into the cost model, take its
        # decision, run the planner-routed compile end to end, and
        # assert the matches are identical either way (the planner can
        # only move time, never results). This is the detail.planner
        # calibration table: predicted vs measured per side, the
        # decision taken, and the chosen side's mispredict.
        from tempo_tpu.search import planner as planner_mod

        planner_mod.configure(enabled=True, reset=True, seed=True)
        try:
            p = planner_mod.PLANNER
            packed_dd = sp.staged_dict.packed
            dict_bytes = packed_dd.real_bytes
            staged_bytes = sp.staged_dict.nbytes
            p.observe("host_probe", compile_ms / 1e3, nbytes=dict_bytes)
            # the measured staging wall is pack (dominant at these
            # cardinalities: millions of strings copied into the byte
            # buffer) PLUS the device put; book it as pack over the real
            # dictionary bytes — stuffing it into the h2d rate would
            # inflate seconds-per-byte 10-100x (the true h2d rate arrives
            # from the seed microbenchmark / live profiler feed)
            p.observe("pack", probe["device_probe_stage_ms"] / 1e3,
                      nbytes=dict_bytes)
            p.observe("device_probe", probe["device_probe_ms"] / 1e3,
                      nbytes=staged_bytes)
            d = p.decide_probe(
                n_vals=len(pages.val_dict), dict_bytes=dict_bytes,
                resident=True, staged_bytes=staged_bytes,
                fp=packed_dd.fingerprint, site="compile")
            cq_plan = compile_query(pages.key_dict, pages.val_dict, req,
                                    packed_vals=packed,
                                    staged_dict=sp.staged_dict)
            p_count, _, p_scores, _p_idx = eng.scan_staged(sp, cq_plan)
            assert int(p_count) == int(count), (
                f"planner-routed scan diverged: {int(p_count)} != "
                f"{int(count)}")
            assert np.array_equal(np.asarray(p_scores),
                                  np.asarray(h_scores)), \
                "planner-routed top-k scores diverged from host path"
            measured = {"host": compile_ms,
                        "device": probe["device_probe_ms"]}
            predicted = {"host": round(d.predicted_host_s * 1e3, 1),
                         "device": round(d.predicted_device_s * 1e3, 1)}
            chosen_meas = measured[d.target]
            snap = p.snapshot(recent=0)
            probe["planner"] = {
                "decision": d.target,
                "took": ("device" if cq_plan.val_hits is not None
                         else "host"),
                "predicted_ms": predicted,
                "measured_ms": measured,
                "mispredict_pct": round(
                    abs(predicted[d.target] - chosen_meas)
                    / max(chosen_meas, 1e-6) * 100, 1),
                "decisions": snap["decisions"],
                "seed_ms": snap["seed_ms"],
            }
        finally:
            planner_mod.configure(enabled=False)
    return rate, int(count), compile_ms, probe


# ---------------------------------------------------------------------------
# Phase registry — each entry runs in its own subprocess via `--phase NAME`.
# Every phase reads its sizes from the same BENCH_* env knobs as before and
# returns a JSON-able dict (the shape that lands in the final detail block).
# ---------------------------------------------------------------------------

def phase_probe():
    """Preflight: prove the device answers, name it, and measure the
    fixed cost of one device→host sync so serving latency can be read
    net of it."""
    import jax
    import jax.numpy as jnp

    probe_fn = jax.jit(lambda x: x + 1)
    int(probe_fn(jnp.int32(1)))  # compile once; loop measures pure sync
    t0 = time.perf_counter()
    for _ in range(5):
        int(probe_fn(jnp.int32(1)))
    sync_ms = (time.perf_counter() - t0) / 5 * 1e3
    dev = jax.devices()[0]
    return {
        "ok": True,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "device": str(dev),
        "sync_ms": round(sync_ms, 2),
    }


def phase_single():
    n_entries = int(os.environ.get("BENCH_ENTRIES", 1_000_000))
    iters = int(os.environ.get("BENCH_ITERS", 20))
    tpu_rate, cpu_rate, matches, dur_rate = bench_single_block(n_entries, iters)
    return {
        "n_entries": n_entries,
        "tpu_traces_per_sec": round(tpu_rate),
        "cpu_traces_per_sec": round(cpu_rate),
        "matches": matches,
        "duration_only_traces_per_sec": round(dur_rate),
    }


def phase_multiblock():
    n_entries = int(os.environ.get("BENCH_ENTRIES", 1_000_000))
    iters = int(os.environ.get("BENCH_ITERS", 20))
    n_blocks = int(os.environ.get("BENCH_BLOCKS", 100))
    rate, matches = bench_multiblock(
        n_blocks, max(1024, n_entries // n_blocks), iters)
    return {"blocks": n_blocks, "traces_per_sec": round(rate),
            "matches": matches}


def phase_serving():
    n_entries = int(os.environ.get("BENCH_ENTRIES", 1_000_000))
    iters = int(os.environ.get("BENCH_ITERS", 20))
    n_blocks = int(os.environ.get("BENCH_BLOCKS", 100))
    rate, p50, p95, dispatches = bench_serving(
        n_blocks, max(1024, n_entries // n_blocks), iters)
    return {"blocks": n_blocks, "traces_per_sec": round(rate),
            "p50_ms": round(p50, 2), "p95_ms": round(p95, 2),
            "scan_dispatches": dispatches}


def _probe_min_vals_env():
    """BENCH_PROBE_MIN_VALS: override the device-probe threshold for the
    high-cardinality phases (0 disables; unset = library default)."""
    raw = os.environ.get("BENCH_PROBE_MIN_VALS")
    return int(raw) if raw not in (None, "") else None


def phase_high_cardinality():
    n_entries = int(os.environ.get("BENCH_ENTRIES", 1_000_000))
    iters = int(os.environ.get("BENCH_ITERS", 20))
    cardinality = int(os.environ.get("BENCH_CARDINALITY", 1_000_000))
    rate, matches, compile_ms, probe = bench_high_cardinality(
        n_entries, cardinality, iters, probe_min_vals=_probe_min_vals_env())
    return {"distinct_values": cardinality, "traces_per_sec": round(rate),
            "dict_prefilter_ms": round(compile_ms, 1), "matches": matches,
            **probe}


def phase_high_cardinality_full():
    # BASELINE config 4 names 10M distinct values — run the prefilter at
    # full cardinality too (the device probe scales with dictionary
    # BYTES, so full cardinality is exactly where it must be measured)
    n_entries = int(os.environ.get("BENCH_ENTRIES", 1_000_000))
    iters = int(os.environ.get("BENCH_ITERS", 20))
    cardinality = int(os.environ.get("BENCH_CARDINALITY_FULL", 10_000_000))
    if not cardinality:
        return None
    rate, matches, compile_ms, probe = bench_high_cardinality(
        n_entries, cardinality, max(3, iters // 4),
        probe_min_vals=_probe_min_vals_env())
    return {"distinct_values": cardinality, "traces_per_sec": round(rate),
            "dict_prefilter_ms": round(compile_ms, 1), "matches": matches,
            **probe}


def phase_coalesced_serving():
    n_entries = int(os.environ.get("BENCH_ENTRIES", 1_000_000))
    iters = int(os.environ.get("BENCH_ITERS", 20))
    n_blocks = int(os.environ.get("BENCH_BLOCKS", 100))
    conc = int(os.environ.get("BENCH_COALESCE_CONCURRENCY", 8))
    return bench_coalesced_serving(
        n_blocks, max(1024, n_entries // n_blocks),
        max(3, iters // 4), concurrency=conc)


def phase_profile_overhead():
    """Dispatch-profiler contract: `search_profiling_enabled: false` is
    a TRUE noop, and the enabled profiler must cost < ~2% on the
    dispatch hot path. Measures the same fully-synchronous scan loop
    with the profiler enabled vs disabled (min-of-reps, interleaved so
    clock drift cancels) and asserts the delta; the enabled run's
    per-stage aggregates ride along for detail.profile."""
    from tempo_tpu import tempopb
    from tempo_tpu.observability import profile
    from tempo_tpu.search.engine import ScanEngine, stage
    from tempo_tpu.search.pipeline import compile_query

    n_entries = int(os.environ.get("BENCH_PROFILE_ENTRIES", 65_536))
    iters = int(os.environ.get("BENCH_PROFILE_ITERS", 150))
    reps = int(os.environ.get("BENCH_PROFILE_REPS", 5))
    pages = build_corpus(n_entries)
    req = tempopb.SearchRequest()
    req.tags["service.name"] = "svc-007"
    req.tags["http.status_code"] = "500"
    req.limit = 20
    cq = compile_query(pages.key_dict, pages.val_dict, req)
    eng = ScanEngine(top_k=128)
    sp = stage(pages)
    eng.scan_staged(sp, cq)  # compile+warm

    def run_loop(n):
        t0 = time.perf_counter()
        for _ in range(n):
            eng.scan_staged(sp, cq)  # sync path: dispatch + D2H, profiled
        return time.perf_counter() - t0

    run_loop(max(1, iters // 5))  # warmup
    t_on, t_off = [], []
    try:
        for _ in range(reps):
            profile.configure(enabled=False)
            t_off.append(run_loop(iters))
            profile.configure(enabled=True)
            t_on.append(run_loop(iters))
    finally:
        profile.configure(enabled=True)
    best_on, best_off = min(t_on), min(t_off)
    ab_overhead_pct = (best_on - best_off) / best_off * 100

    # The A/B wall-clock delta above is the honest end-to-end number but
    # on a shared host its noise floor (several %) swamps a ~50us/call
    # effect. The ASSERTED bound is deterministic: time the exact record
    # protocol an enabled dispatch adds (alloc + stage timers +
    # compile_check + publish) against the noop path, and take it as a
    # fraction of the measured per-dispatch time.
    def protocol_loop(n):
        t0 = time.perf_counter()
        for i in range(n):
            with profile.dispatch("single") as rec:
                with rec.stage("build"):
                    pass
                rec.compile_check(("overhead_probe", i % 8))
                with rec.stage("execute"):
                    pass
                with rec.stage("d2h"):
                    pass
                rec.add_bytes(d2h=64)
        return time.perf_counter() - t0

    N_PROTO = 20_000
    protocol_loop(1000)  # warm
    record_us = min(protocol_loop(N_PROTO) for _ in range(3)) \
        / N_PROTO * 1e6
    profile.configure(enabled=False)
    try:
        noop_us = min(protocol_loop(N_PROTO) for _ in range(3)) \
            / N_PROTO * 1e6
    finally:
        profile.configure(enabled=True)
    dispatch_us = best_on / iters * 1e6
    overhead_pct = (record_us - noop_us) / dispatch_us * 100

    snap = profile.PROFILER.snapshot(recent=0)
    result = {
        "n_entries": n_entries,
        "iters_per_rep": iters,
        "reps": reps,
        "enabled_s": round(best_on, 4),
        "disabled_s": round(best_off, 4),
        "ab_overhead_pct": round(ab_overhead_pct, 3),
        "record_cost_us": round(record_us - noop_us, 2),
        "noop_cost_us": round(noop_us, 3),
        "dispatch_us": round(dispatch_us, 1),
        "overhead_pct": round(overhead_pct, 3),
        "within_2pct": overhead_pct < 2.0,
        "jit_cache": snap["jit_cache"],
    }
    assert overhead_pct < 2.0, (
        f"profiling record cost {record_us - noop_us:.1f}us is "
        f"{overhead_pct:.2f}% of the {dispatch_us:.0f}us dispatch — "
        "exceeds the 2% budget")
    # The wall-clock A/B delta rides a ±6% noise floor on shared CPU
    # hosts (two interleaved 150-iteration loops cannot resolve a
    # ~50us/call effect there), so its assert is OPT-IN: set
    # BENCH_PROFILE_AB_ASSERT=1 on quiet/pinned hosts to enforce it;
    # tier-1 and default bench runs keep only the deterministic
    # protocol-cost assert above.
    ab_assert = os.environ.get("BENCH_PROFILE_AB_ASSERT", "") \
        not in ("", "0")
    result["ab_assert_enabled"] = ab_assert
    if ab_assert:
        assert ab_overhead_pct < 6.0, (
            f"enabled-vs-disabled wall clock regressed "
            f"{ab_overhead_pct:.2f}% (> 6% even allowing for noise)")
    return result


def phase_query_stats_overhead():
    """Per-query inspector contract (docs/search-query-stats.md):
    `search_query_stats_enabled: false` is a TRUE noop — byte-identical
    results either way — and the enabled per-query record protocol
    (begin + contextvar activation + the typical per-group records +
    finish/publish) must cost < 2% of a query. Same shape as
    profile_overhead: the asserted bound is the deterministic protocol
    cost (the wall A/B delta rides along, informational)."""
    from tempo_tpu import tempopb
    from tempo_tpu.search import query_stats
    from tempo_tpu.search.batcher import BlockBatcher, ScanJob

    n_entries = int(os.environ.get("BENCH_QSTATS_ENTRIES", 65_536))
    iters = int(os.environ.get("BENCH_QSTATS_ITERS", 60))
    n_blocks = 4
    blocks = [build_corpus(max(1024, n_entries // n_blocks), seed=s)
              for s in range(n_blocks)]

    def mk_jobs():
        jobs = []
        for i, b in enumerate(blocks):
            hdr = dict(b.header)
            jobs.append(ScanJob(
                key=(f"qs-{i}", 0, b.n_pages),
                pages_fn=(lambda b=b: b), header=hdr,
                n_pages=b.n_pages, n_entries=hdr["n_entries"],
                geometry=(hdr["entries_per_page"], hdr["kv_per_entry"])))
        return jobs

    req = tempopb.SearchRequest()
    req.tags["service.name"] = "svc-007"
    req.tags["http.status_code"] = "500"
    req.limit = 20
    batcher = BlockBatcher()
    jobs = mk_jobs()

    def one_query(enabled: bool):
        qs = query_stats.begin("bench", req) if enabled else None
        with query_stats.activate(qs):
            res = batcher.search(jobs, req)
        if qs is not None:
            qs.finish()
        return res.response()

    query_stats.configure(enabled=True)
    warm = one_query(True)  # stage + compile
    t_on, t_off = [], []
    r_on = r_off = None
    try:
        for _ in range(3):
            query_stats.configure(enabled=False)
            t0 = time.perf_counter()
            for _ in range(iters):
                r_off = one_query(False)
            t_off.append(time.perf_counter() - t0)
            query_stats.configure(enabled=True)
            t0 = time.perf_counter()
            for _ in range(iters):
                r_on = one_query(True)
            t_on.append(time.perf_counter() - t0)
    finally:
        query_stats.configure(enabled=True)
    query_us = min(t_on) / iters * 1e6
    ab_overhead_pct = (min(t_on) - min(t_off)) / min(t_off) * 100

    # byte-identity: the disabled and enabled paths must return the
    # same traces, and the LEGACY metrics must match exactly — only the
    # stats fields may differ
    def strip(resp):
        r = tempopb.SearchResponse()
        r.CopyFrom(resp)
        r.metrics.device_seconds = 0
        r.metrics.inspected_bytes_device = 0
        r.metrics.query_stats_json = ""
        return r.SerializeToString()

    identical = strip(r_on) == strip(r_off) == strip(warm)
    assert identical, "query-stats on/off responses diverged"

    # deterministic protocol cost: the exact per-query record sequence
    # a 4-group search performs, enabled vs disabled
    def protocol_loop(n):
        t0 = time.perf_counter()
        for _ in range(n):
            qs = query_stats.begin("bench", req)
            with query_stats.activate(qs):
                inner = query_stats.current()
                if inner is not None:
                    for _g in range(4):
                        inner.add_cache("hbm_hit")
                        inner.add_inspected(blocks=1, nbytes=4096)
                        inner.add_device_stages({"execute": 1e-6},
                                                fused_q=2)
                        inner.add_device_stages({"d2h": 1e-7},
                                                count=False)
                    inner.add_skip("time_range", 2)
                    for st in ("header_prune", "staging", "prepare",
                               "dispatch", "drain"):
                        inner.add_stage(st, 1e-6)
            if qs is not None:
                qs.finish()
        return time.perf_counter() - t0

    N_PROTO = 5_000
    protocol_loop(500)  # warm
    query_stats.configure(enabled=True)
    record_us = min(protocol_loop(N_PROTO) for _ in range(3)) \
        / N_PROTO * 1e6
    query_stats.configure(enabled=False)
    try:
        noop_us = min(protocol_loop(N_PROTO) for _ in range(3)) \
            / N_PROTO * 1e6
    finally:
        query_stats.configure(enabled=True)
    overhead_pct = (record_us - noop_us) / query_us * 100
    result = {
        "n_entries": n_entries,
        "iters_per_rep": iters,
        "query_us": round(query_us, 1),
        "record_cost_us": round(record_us - noop_us, 2),
        "noop_cost_us": round(noop_us, 3),
        "overhead_pct": round(overhead_pct, 3),
        "ab_overhead_pct": round(ab_overhead_pct, 3),
        "within_2pct": overhead_pct < 2.0,
        "byte_identical": identical,
    }
    assert overhead_pct < 2.0, (
        f"query-stats record cost {record_us - noop_us:.1f}us is "
        f"{overhead_pct:.2f}% of the {query_us:.0f}us query — exceeds "
        "the 2% budget")
    return result


def phase_selftrace_overhead():
    """Dogfood pipeline contract (`selftrace_ingest_enabled`,
    docs/observability.md "Self-hosted tracing"): the gate off is a
    TRUE noop — byte-identical search responses — and the gate ON must
    cost < 2% of an end-to-end request. The request-path additions are
    (a) per-dispatch stage-span lowering, (b) the request span's
    query.* annotation, (c) the breaker/recorder gate reads; export +
    self-ingest ride the flush thread, off the request path. Same shape
    as profile_overhead: the ASSERTED bound is the deterministic
    protocol cost as a fraction of a measured request; the wall-clock
    A/B delta rides along, informational."""
    import json as _json
    import tempfile

    from tempo_tpu.api.http import HTTPApi
    from tempo_tpu.db.tempodb import TempoDBConfig
    from tempo_tpu.modules import App, AppConfig
    from tempo_tpu.observability import selftrace
    from tempo_tpu.observability.selftrace import SELFTRACE
    from tempo_tpu.utils.ids import random_trace_id
    from tempo_tpu.utils.test_data import make_trace

    iters = int(os.environ.get("BENCH_SELFTRACE_ITERS", 40))
    reps = int(os.environ.get("BENCH_SELFTRACE_REPS", 3))
    with tempfile.TemporaryDirectory(prefix="bench-selftrace-") as tmp:
        app = App(AppConfig(
            wal_dir=os.path.join(tmp, "wal"),
            db=TempoDBConfig(auto_mesh=False),
            self_tracing={"enabled": True, "exporter": "self",
                          "selftrace_ingest_enabled": True,
                          "sample_ratio": 1.0,
                          # keep the batch thread quiet mid-timing;
                          # force_flush drains between reps
                          "flush_interval_s": 3600.0}))
        try:
            api = HTTPApi(app)
            for seed in range(1, 5):
                app.push("t1", list(make_trace(random_trace_id(),
                                               seed=seed).batches))
            app.flush_tick(force=True)
            app.poll_tick()
            params = {"tags": "service.name=frontend", "limit": "20"}
            hdr = {"X-Scope-OrgID": "t1"}

            def run_loop(n):
                body = None
                t0 = time.perf_counter()
                for _ in range(n):
                    code, body = api.handle("GET", "/api/search",
                                            params, hdr)
                    assert code == 200
                return time.perf_counter() - t0, body

            run_loop(max(4, iters // 4))  # warm: jit cache + heat
            t_on, t_off = [], []
            b_on = b_off = None
            try:
                for _ in range(reps):
                    selftrace.configure(ingest_enabled=False)
                    dt, b_off = run_loop(iters)
                    t_off.append(dt)
                    selftrace.configure(ingest_enabled=True)
                    dt, b_on = run_loop(iters)
                    t_on.append(dt)
                    app.tracer.processor.force_flush()
            finally:
                selftrace.configure(ingest_enabled=True)
            request_us = min(t_on) / iters * 1e6
            ab_overhead_pct = (min(t_on) - min(t_off)) / min(t_off) * 100
            identical = (_json.dumps(b_on, sort_keys=True)
                         == _json.dumps(b_off, sort_keys=True))
            assert identical, "selftrace gate on/off responses diverged"

            # deterministic protocol cost: exactly what the gate adds
            # to one request — annotate the request span with the
            # QueryStats headline dict — measured enabled vs disabled
            # (the span and its observed dispatch.<stage> children
            # exist either way under plain self-tracing)
            qd = {"wall_ms": 2.0, "device_seconds": 4e-4,
                  "blocks_inspected": 4,
                  "bytes_inspected": {"host": 1 << 16, "device": 1 << 18},
                  "dispatches": 2, "fused_dispatches": 1}
            tracer = app.tracer

            def protocol_loop(n):
                t0 = time.perf_counter()
                for _ in range(n):
                    with tracer.start_span("bench.request"):
                        SELFTRACE.annotate_query(qd)
                return time.perf_counter() - t0

            N_PROTO = 5_000
            protocol_loop(500)  # warm
            on_us = min(protocol_loop(N_PROTO) for _ in range(3)) \
                / N_PROTO * 1e6
            selftrace.configure(ingest_enabled=False)
            try:
                off_us = min(protocol_loop(N_PROTO) for _ in range(3)) \
                    / N_PROTO * 1e6
            finally:
                selftrace.configure(ingest_enabled=True)
            overhead_pct = (on_us - off_us) / request_us * 100
            result = {
                "iters_per_rep": iters,
                "reps": reps,
                "request_us": round(request_us, 1),
                "gate_cost_us": round(on_us - off_us, 2),
                "noop_cost_us": round(off_us, 3),
                "overhead_pct": round(overhead_pct, 3),
                "ab_overhead_pct": round(ab_overhead_pct, 3),
                "within_2pct": overhead_pct < 2.0,
                "byte_identical": identical,
            }
            assert overhead_pct < 2.0, (
                f"selftrace gate cost {on_us - off_us:.1f}us is "
                f"{overhead_pct:.2f}% of the {request_us:.0f}us request "
                "— exceeds the 2% budget")
        finally:
            app.shutdown()
    return result


def phase_freshness():
    """Search-freshness SLO (ROADMAP item 4's acceptance instrument):
    drive a soak-style concurrent write load through the full
    distributor -> ingester -> WAL -> flush -> poll pipeline and
    measure push->searchable end to end with REAL canary round trips.
    Contracts asserted every round:

      - the white-box freshness gauge (tempo_search_freshness_seconds,
        stamped at poll from block end_times) and the black-box canary
        measurement agree within one poll interval;
      - `ingest_telemetry_enabled: false` is a TRUE noop — the WAL
        bytes a push produces are identical on/off;
      - the enabled telemetry record protocol costs < 2% of a push ack.
    """
    import tempfile
    import threading

    from tempo_tpu.modules import App, AppConfig
    from tempo_tpu.observability import ingest_telemetry
    from tempo_tpu.observability import metrics as obs
    from tempo_tpu.observability.ingest_telemetry import (
        TELEMETRY, IngestCanary)
    from tempo_tpu.utils.test_data import make_trace

    soak_s = float(os.environ.get("BENCH_FRESH_SECONDS", 6.0))
    writers = int(os.environ.get("BENCH_FRESH_WRITERS", 2))
    probes = int(os.environ.get("BENCH_FRESH_PROBES", 6))
    flush_every = float(os.environ.get("BENCH_FRESH_FLUSH_S", 0.25))
    poll_every = float(os.environ.get("BENCH_FRESH_POLL_S", 0.5))

    from tempo_tpu.modules import Limits

    tmp = tempfile.mkdtemp(prefix="bench-freshness-")
    # soak limits: the phase measures the pipeline, not tenant pushback
    lim = Limits(ingestion_rate_bytes=1 << 30,
                 ingestion_burst_bytes=1 << 30,
                 max_live_traces=1_000_000)
    app = App(AppConfig(wal_dir=os.path.join(tmp, "wal"),
                        ingest_telemetry_enabled=True, limits=lim))

    def _now_trace(seed: int):
        """A make_trace stamped NOW: the freshness gauge derives from
        block end_times, so soak spans must carry real wall clock."""
        tr = make_trace(os.urandom(16), seed=seed)
        now_ns = time.time_ns()
        for b in tr.batches:
            for ss in b.scope_spans:
                for sp in ss.spans:
                    dur = max(1, (sp.end_time_unix_nano
                                  - sp.start_time_unix_nano)
                              % 1_000_000_000)
                    sp.start_time_unix_nano = now_ns - dur
                    sp.end_time_unix_nano = now_ns
        return tr

    stop = threading.Event()
    pushed = [0] * writers

    def writer(w: int) -> None:
        i = 0
        while not stop.is_set():
            tr = _now_trace(w * 1_000_003 + i)
            try:
                app.push(f"soak-{w}", list(tr.batches))
                pushed[w] += 1
            except Exception:  # noqa: BLE001 — limits under soak are fine
                pass
            i += 1
            # yield: a zero-sleep loop per writer starves the GIL and
            # turns the measurement into a scheduler bench — the load
            # should stress the pipeline, not freeze the poll loop
            time.sleep(0.001)

    def maintenance() -> None:
        last_poll = 0.0
        while not stop.wait(flush_every):
            try:
                app.flush_tick(force=True)
                if time.monotonic() - last_poll >= poll_every:
                    app.poll_tick()
                    last_poll = time.monotonic()
            except Exception:  # noqa: BLE001 — keep the loop alive
                pass

    threads = [threading.Thread(target=writer, args=(w,), daemon=True)
               for w in range(writers)]
    threads.append(threading.Thread(target=maintenance, daemon=True))
    soak_t0 = time.monotonic()
    for t in threads:
        t.start()

    canary = IngestCanary(app.push, app.reader_db.search,
                          tenant="canary", poll_step_s=0.05)
    # warmup probe (not sampled): the FIRST canary search pays the scan
    # kernels' XLA compile, which belongs to the query path, not the
    # write path this phase measures — steady-state probes hit the
    # compile cache like a real deployment's standing canary
    canary.probe_once(timeout_s=60.0)
    canary.probes = canary.failures = 0
    samples: list[float] = []
    gauge_diffs: list[float] = []
    deadline = time.monotonic() + max(soak_s, probes * 2.0) + 30.0
    while len(samples) + canary.failures < probes \
            and time.monotonic() < deadline:
        f = canary.probe_once(timeout_s=15.0)
        if f is None:
            continue
        samples.append(f)
        # the gauge was stamped at the poll that made the canary block
        # visible: it and the measured round trip may differ by at most
        # the time between that poll and the probe's next check — one
        # poll interval (+ the probe's own step)
        gauge = obs.search_freshness.value(tenant="canary")
        if gauge:
            gauge_diffs.append(abs(gauge - f))
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    # writers run until the probe loop finishes (warmup included), so
    # the rate divides by the ACTUAL elapsed soak wall time — dividing
    # by the nominal soak_s would overstate it by the probe duration
    soak_elapsed = time.monotonic() - soak_t0
    soak_pushed = sum(pushed)

    # ---- ack-overhead contract: telemetry record protocol < 2% ----
    # per-push ack time measured enabled (the shipping default), then
    # the EXACT protocol an enabled push adds (one enabled-check + two
    # perf_counter reads + one histogram observe) timed against the
    # disabled path — deterministic, immune to shared-host noise
    # (profile_overhead's lesson)
    N_ACK = int(os.environ.get("BENCH_FRESH_ACK_ITERS", 300))
    # distinct trace ids per push: re-pushing one id appends to the same
    # live trace until max_bytes_per_trace turns the loop into a limit
    # bench instead of an ack bench
    ack_batches = [list(_now_trace(i).batches) for i in range(64)]

    def ack_loop(n):
        t0 = time.perf_counter()
        for i in range(n):
            app.push("ackbench", ack_batches[i % len(ack_batches)])
        return time.perf_counter() - t0

    ack_loop(30)  # warm
    push_us = min(ack_loop(N_ACK) for _ in range(3)) / N_ACK * 1e6

    def protocol_loop(n):
        t0 = time.perf_counter()
        for _ in range(n):
            if TELEMETRY.enabled:
                t1 = time.perf_counter()
                TELEMETRY.record_push_ack(time.perf_counter() - t1)
        return time.perf_counter() - t0

    N_PROTO = 20_000
    protocol_loop(1000)
    record_us = min(protocol_loop(N_PROTO) for _ in range(3)) \
        / N_PROTO * 1e6
    ingest_telemetry.configure(enabled=False)
    try:
        noop_us = min(protocol_loop(N_PROTO) for _ in range(3)) \
            / N_PROTO * 1e6
    finally:
        ingest_telemetry.configure(enabled=True)
    overhead_pct = (record_us - noop_us) / push_us * 100

    # ---- noop contract: identical WAL bytes with telemetry off ----
    def wal_bytes(enabled: bool) -> bytes:
        ingest_telemetry.configure(enabled=enabled)
        try:
            a = App(AppConfig(
                wal_dir=os.path.join(tmp, f"noop-{enabled}"),
                ingest_telemetry_enabled=enabled))
            for i in range(8):
                tr = make_trace(bytes([i]) * 16, seed=i)
                a.push("noop", list(tr.batches))
            for ing in a.ingesters.values():
                ing.instance("noop").cut_complete_traces(force=True)
            inst = next(iter(a.ingesters.values())).instance("noop")
            with open(inst.head.path, "rb") as f:
                data = f.read()
            with open(inst.head.path + ".search", "rb") as f:
                return data + b"\x00SEARCH\x00" + f.read()
        finally:
            ingest_telemetry.configure(enabled=True)

    byte_identical = wal_bytes(True) == wal_bytes(False)

    # ---- hot-tier gate-on leg (search-live-tail.md): push→searchable
    # through the live tier, NO flush/poll maintenance at all — the
    # rolling stage alone must make a push searchable, under the same
    # soak write load as the baseline leg above. The canary probes the
    # FULL app search path (frontend → ingester leg → hot scan), not
    # the reader TempoDB, which only sees flushed blocks.
    from tempo_tpu.db.tempodb import TempoDBConfig
    from tempo_tpu.search.live_tier import LIVE_TIER

    live_probes = int(os.environ.get("BENCH_FRESH_LIVE_PROBES", probes))
    app2 = App(AppConfig(
        wal_dir=os.path.join(tmp, "wal-live"),
        db=TempoDBConfig(search_live_tier_enabled=True),
        ingest_telemetry_enabled=True, limits=lim))
    stop2 = threading.Event()
    pushed2 = [0] * writers

    def live_writer(w: int) -> None:
        i = 0
        while not stop2.is_set():
            tr = _now_trace(w * 1_000_003 + i)
            try:
                app2.push(f"soak-{w}", list(tr.batches))
                pushed2[w] += 1
            except Exception:  # noqa: BLE001 — limits under soak are fine
                pass
            i += 1
            time.sleep(0.001)

    threads2 = [threading.Thread(target=live_writer, args=(w,),
                                 daemon=True) for w in range(writers)]
    live_t0 = time.monotonic()
    for t in threads2:
        t.start()
    live_canary = IngestCanary(app2.push, app2.search, tenant="canary",
                               poll_step_s=0.01)
    # warmup probe (not sampled): first gate-on search pays the hot
    # kernel's XLA compile — steady state hits the compile cache
    live_canary.probe_once(timeout_s=60.0)
    live_canary.probes = live_canary.failures = 0
    live_samples: list[float] = []
    live_deadline = time.monotonic() + max(soak_s, live_probes * 2.0) + 30.0
    while len(live_samples) + live_canary.failures < live_probes \
            and time.monotonic() < live_deadline:
        f = live_canary.probe_once(timeout_s=15.0)
        if f is not None:
            live_samples.append(f)

    # ---- live_tail sub-phase: standing-query push→notify latency
    # under the same soak load — the subscription is evaluated inside
    # the push micro-batch, so notify lands before the push ack
    from tempo_tpu import tempopb as _pb

    tail_req = _pb.SearchRequest()
    tail_req.tags["service.name"] = "tempo-canary"
    tail_sub = app2.tail_subscribe("canary", tail_req)
    tail_samples: list[float] = []
    tail_missed = 0
    if tail_sub is not None:
        for _ in range(live_probes):
            t0 = time.monotonic()
            app2.push("canary",
                      [live_canary._make_batch("tail-bench")])
            if tail_sub.poll(timeout_s=5.0):
                tail_samples.append(time.monotonic() - t0)
            else:
                tail_missed += 1
        app2.tail_unsubscribe(tail_sub)
    stop2.set()
    for t in threads2:
        t.join(timeout=10.0)
    live_elapsed = time.monotonic() - live_t0
    try:
        app2.shutdown()
    except Exception:  # noqa: BLE001 — bench teardown best-effort
        pass
    # later phases measure the gate-off default; don't leak the tier
    LIVE_TIER.configure(enabled=False)

    def _pct(vals, p):
        if not vals:
            return None
        vals = sorted(vals)
        return round(vals[min(len(vals) - 1, int(p * len(vals)))], 3)

    live_p99 = _pct(live_samples, 0.99)
    tail_p99 = _pct(tail_samples, 0.99)

    samples.sort()

    def pct(p):
        if not samples:
            return None
        return round(samples[min(len(samples) - 1,
                                 int(p * len(samples)))], 3)

    max_diff = round(max(gauge_diffs), 3) if gauge_diffs else None
    # tolerance: one poll interval (the agreement contract) + 1s for the
    # gauge's inherent quantization (BlockMeta.end_time is unix SECONDS,
    # so the gauge floors the push time) + scheduling margin
    tolerance = poll_every + 1.0 + 0.25
    agree = max_diff is not None and max_diff <= tolerance
    result = {
        "soak_s": round(soak_elapsed, 2),
        "writers": writers,
        "traces_pushed": soak_pushed,
        "push_rate_per_s": round(soak_pushed / max(soak_elapsed, 1e-9), 1),
        "flush_interval_s": flush_every,
        "poll_interval_s": poll_every,
        "probes": canary.probes,
        "probe_failures": canary.failures,
        "push_to_searchable_p50_s": pct(0.50),
        "push_to_searchable_p99_s": pct(0.99),
        "gauge_vs_canary_max_diff_s": max_diff,
        "gauge_agrees_within_poll": agree,
        "push_ack_us": round(push_us, 1),
        "record_cost_us": round(record_us - noop_us, 3),
        "overhead_pct": round(overhead_pct, 3),
        "within_2pct": overhead_pct < 2.0,
        "byte_identical": byte_identical,
        # hot-tier gate-on leg: no maintenance loop at all — the rolling
        # stage alone answers, so these numbers ARE the tier's freshness
        "live_tier": {
            "soak_s": round(live_elapsed, 2),
            "traces_pushed": sum(pushed2),
            "probes": live_canary.probes,
            "probe_failures": live_canary.failures,
            "push_to_searchable_p50_s": _pct(live_samples, 0.50),
            "push_to_searchable_p99_s": live_p99,
        },
        "live_tail": {
            "notified": len(tail_samples),
            "missed": tail_missed,
            "push_to_notify_p50_s": _pct(tail_samples, 0.50),
            "push_to_notify_p99_s": tail_p99,
        },
    }
    assert samples, (
        f"no canary probe became searchable ({canary.failures} failures: "
        f"{canary.last_error}) — the flush/poll pipeline is wedged")
    assert agree, (
        f"freshness gauge and canary disagree by {max_diff}s — more than "
        f"one poll interval ({poll_every}s) + the 1s end_time "
        "quantization")
    assert byte_identical, (
        "telemetry on/off produced different WAL bytes — the noop "
        "contract is broken")
    assert overhead_pct < 2.0, (
        f"ingest telemetry record cost {record_us - noop_us:.2f}us is "
        f"{overhead_pct:.2f}% of the {push_us:.0f}us push ack — exceeds "
        "the 2% budget")
    assert live_samples, (
        f"no gate-on canary probe became searchable through the hot "
        f"tier ({live_canary.failures} failures: "
        f"{live_canary.last_error}) — the live tier is wedged")
    # the tentpole SLO: the hot tier answers WITHOUT waiting for
    # flush+poll, so push→searchable collapses from the multi-second
    # maintenance cadence to the push ack + one hot scan
    assert live_p99 is not None and live_p99 < 0.25, (
        f"hot-tier push→searchable p99 {live_p99}s exceeds the 250ms "
        "gate-on budget — the rolling stage is not absorbing pushes "
        "or the scan is falling back")
    assert tail_sub is not None and not tail_missed, (
        f"live tail missed {tail_missed} of {live_probes} standing-"
        "query notifications (sub registered: "
        f"{tail_sub is not None})")
    return result


def phase_chaos():
    """Robustness contract (docs/robustness.md, ISSUE 9 acceptance):

      (a) noop: with the breaker OFF and no faultpoint armed, the
          dispatch guard's protocol cost is < 2% of a dispatch
          (deterministic measurement, the PR 5/7/8 pattern) and
          responses are byte-identical to the breaker-ON healthy run
          (canonicalized: device_seconds is measured wall time).
      (b) chaos soak: a device hang injected MID-SOAK must keep p99
          bounded by the watchdog (no hung thread), sustain throughput
          through the byte-identical host fallback, trip the breaker
          (device_wedged: true sourced from BREAKER STATE, not ad-hoc
          probing), and recover through half-open after the fault
          clears.
    """
    import json as _json
    import tempfile

    from tempo_tpu import robustness, tempopb
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.backend.types import (
        BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER,
    )
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.encoding.v2.compression import compress
    from tempo_tpu.observability import metrics as obs
    from tempo_tpu.observability.profile import device_status

    n_blocks = int(os.environ.get("BENCH_CHAOS_BLOCKS", 16))
    entries_per_block = int(os.environ.get("BENCH_CHAOS_ENTRIES", 16_384))
    rounds = int(os.environ.get("BENCH_CHAOS_ROUNDS", 15))
    watchdog_s = float(os.environ.get("BENCH_CHAOS_WATCHDOG_S", 0.5))
    total = n_blocks * entries_per_block

    def canon(resp):
        r = tempopb.SearchResponse()
        r.CopyFrom(resp)
        # measured wall time / placement split move by design —
        # identity is about the ANSWER (traces + deterministic metrics)
        r.metrics.device_seconds = 0.0
        r.metrics.inspected_bytes_device = 0
        return r.SerializeToString()

    with tempfile.TemporaryDirectory() as td:
        be = LocalBackend(td + "/blocks")
        db = TempoDB(be, td + "/wal", TempoDBConfig(
            search_breaker_enabled=True,
            search_breaker_fault_threshold=3,
            search_breaker_cooldown_s=0.5,
            search_device_dispatch_timeout_s=watchdog_s))
        metas = []
        for s in range(n_blocks):
            pages = build_corpus(entries_per_block, seed=s)
            m = BlockMeta(tenant_id="bench", encoding="none")
            blob = compress(pages.to_bytes(), "none")
            hdr = dict(pages.header)
            hdr["encoding"] = "none"
            hdr["compressed_size"] = len(blob)
            be.write("bench", m.block_id, NAME_SEARCH, blob)
            be.write("bench", m.block_id, NAME_SEARCH_HEADER,
                     _json.dumps(hdr).encode())
            metas.append(m)
        db.blocklist.update("bench", add=metas)

        req = tempopb.SearchRequest()
        req.tags["service.name"] = "svc-007"
        req.tags["http.status_code"] = "500"
        req.limit = 20
        robustness.BREAKER.reset()
        r = db.search("bench", req)
        assert r.metrics.inspected_traces == total
        base = canon(db.search("bench", req).response())

        def run_rounds(n):
            lats = []
            for _ in range(n):
                t0 = time.perf_counter()
                got = canon(db.search("bench", req).response())
                lats.append(time.perf_counter() - t0)
                assert got == base, "response diverged from baseline"
            lats.sort()
            return lats

        # ---- healthy baseline (breaker ON, closed) ----
        healthy = run_rounds(rounds)
        healthy_p50 = healthy[len(healthy) // 2]
        healthy_p99 = healthy[-1]

        # ---- (a) noop contract: breaker OFF ----
        robustness.BREAKER.enabled = False
        assert not robustness.GUARD.active
        off = canon(db.search("bench", req).response())
        noop_identical = off == base
        assert noop_identical, "breaker-off response diverged"
        # deterministic guard protocol cost: the inactive guard is two
        # attribute reads + a lambda call — time it against the bare
        # call and take it as a fraction of a measured dispatch
        N_PROTO = 50_000

        def fn():
            return None

        def loop_guarded(n):
            g = robustness.GUARD
            t0 = time.perf_counter()
            for _ in range(n):
                g.run("bench_probe", fn)
            return time.perf_counter() - t0

        def loop_bare(n):
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            return time.perf_counter() - t0

        loop_guarded(1000), loop_bare(1000)  # warm
        guard_us = min(loop_guarded(N_PROTO) for _ in range(3)) \
            / N_PROTO * 1e6
        bare_us = min(loop_bare(N_PROTO) for _ in range(3)) \
            / N_PROTO * 1e6
        dispatch_us = healthy_p50 * 1e6
        overhead_pct = (guard_us - bare_us) / dispatch_us * 100
        assert overhead_pct < 2.0, (
            f"guard protocol cost {guard_us - bare_us:.2f}us is "
            f"{overhead_pct:.3f}% of the {dispatch_us:.0f}us query — "
            "exceeds the 2% noop budget")
        robustness.BREAKER.enabled = True

        # ---- (b) chaos soak: wedge mid-soak ----
        robustness.BREAKER.reset()
        fallback0 = obs.scan_dispatches.value(mode="host_fallback")
        robustness.FAULTS.arm("device_dispatch_hang",
                              delay_s=watchdog_s * 20, count=10_000)
        t_wedge0 = time.perf_counter()
        wedged = run_rounds(rounds)
        wedge_wall = time.perf_counter() - t_wedge0
        dstat = device_status()
        device_wedged = bool(dstat.get("wedged"))
        breaker_during = dstat.get("breaker", {})
        robustness.FAULTS.disarm_all()
        wedged_p99 = wedged[-1]
        fallback_n = (obs.scan_dispatches.value(mode="host_fallback")
                      - fallback0)
        # bounded: worst round pays at most the watchdog (+ host scan);
        # after the breaker trips rounds are pure host-fallback speed
        bound = watchdog_s * 3 + max(1.0, 10 * healthy_p99)
        assert wedged_p99 < bound, (
            f"wedged p99 {wedged_p99:.2f}s exceeds bound {bound:.2f}s — "
            "the hang leaked into the serving path")
        assert device_wedged, (
            "breaker never tripped during injection (device_wedged "
            "should read true from breaker state)")
        assert fallback_n >= 1, "no host-fallback dispatch recorded"

        # ---- recovery after un-wedge ----
        deadline = time.time() + 30
        recovered = False
        while time.time() < deadline:
            got = canon(db.search("bench", req).response())
            assert got == base
            if robustness.BREAKER.state == "closed":
                recovered = True
                break
            time.sleep(0.1)
        snap = robustness.BREAKER.snapshot()
        assert recovered, f"breaker never recovered: {snap}"
        assert snap["transitions"].get("open->half_open", 0) >= 1
        assert snap["transitions"].get("half_open->closed", 0) >= 1
        robustness.BREAKER.reset()

        return {
            "blocks": n_blocks,
            "rounds": rounds,
            "watchdog_s": watchdog_s,
            "healthy_p50_ms": round(healthy_p50 * 1e3, 2),
            "healthy_p99_ms": round(healthy_p99 * 1e3, 2),
            "wedged_p50_ms": round(wedged[len(wedged) // 2] * 1e3, 2),
            "wedged_p99_ms": round(wedged_p99 * 1e3, 2),
            "wedged_p99_bound_ms": round(bound * 1e3, 1),
            "fallback_traces_per_sec": round(
                total * rounds / wedge_wall),
            "host_fallback_dispatches": int(fallback_n),
            "device_wedged": device_wedged,
            "breaker_during_injection": breaker_during,
            "breaker_transitions": snap["transitions"],
            "noop_identical": noop_identical,
            "guard_cost_us": round(guard_us - bare_us, 3),
            "noop_overhead_pct": round(overhead_pct, 4),
            "within_2pct": overhead_pct < 2.0,
            "recovered": recovered,
        }


def phase_ownership():
    """Owner-routed HBM contract (docs/search-hbm-ownership.md,
    ISSUE 11 acceptance): simulated two-owner serving over ONE shared
    hot blocklist whose staged footprint exceeds a single host's HBM
    budget.

      - independent caches (ownership OFF): both hosts serve the full
        stream over the full blocklist under the same budget — the LRU
        thrashes the shared hot set and every round re-stages;
      - owner-routed (ON): each host stages only its owned placement
        groups (which fit the budget) and serves the rest through the
        byte-identical host route — strictly fewer re-stage bytes and a
        higher HBM hit ratio, with responses byte-identical to OFF.
    """
    import json as _json
    import tempfile

    from tempo_tpu import tempopb
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.backend.types import (
        BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER,
    )
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.encoding.v2.compression import compress
    from tempo_tpu.observability import metrics as obs
    from tempo_tpu.search import ownership

    n_blocks = int(os.environ.get("BENCH_OWNERSHIP_BLOCKS", 24))
    entries_per_block = int(os.environ.get("BENCH_OWNERSHIP_ENTRIES", 8192))
    rounds = int(os.environ.get("BENCH_OWNERSHIP_ROUNDS", 6))
    budget_frac = float(os.environ.get("BENCH_OWNERSHIP_BUDGET_FRAC", 0.55))

    def canon(resp):
        r = tempopb.SearchResponse()
        r.CopyFrom(resp)
        r.metrics.device_seconds = 0.0
        r.metrics.inspected_bytes_device = 0
        return r.SerializeToString()

    with tempfile.TemporaryDirectory() as td:
        be = LocalBackend(td + "/blocks")
        metas = []
        for s in range(n_blocks):
            pages = build_corpus(entries_per_block, E=256, seed=s)
            # unique trace ids: the identity assert compares MERGED
            # results, and build_corpus's all-zero ids would collapse
            # every entry into one trace whose merge winner depends on
            # group completion order, not on routing
            rng = np.random.default_rng(10_000 + s)
            pages.trace_ids = rng.integers(
                0, 255, size=pages.trace_ids.shape, dtype=np.uint8)
            m = BlockMeta(tenant_id="bench", encoding="none")
            blob = compress(pages.to_bytes(), "none")
            hdr = dict(pages.header)
            hdr["encoding"] = "none"
            hdr["compressed_size"] = len(blob)
            be.write("bench", m.block_id, NAME_SEARCH, blob)
            be.write("bench", m.block_id, NAME_SEARCH_HEADER,
                     _json.dumps(hdr).encode())
            metas.append(m)

        req = tempopb.SearchRequest()
        req.tags["service.name"] = "svc-007"
        req.limit = 10_000  # never early-quits: every group is served

        def mkdb(tag, budget):
            # small groups (few blocks each) so ownership has real
            # granularity to split; coalescing off — serial stream
            db = TempoDB(be, f"{td}/wal-{tag}", TempoDBConfig(
                auto_mesh=False,
                search_max_batch_pages=64,
                search_batch_cache_bytes=budget,
                search_coalesce_max_queries=0))
            db.blocklist.update("bench", add=metas)
            return db

        # sizing pass: the full blocklist's staged footprint
        sizer = mkdb("size", 64 << 30)
        sizer.search("bench", req)
        hot_set_bytes = sizer.batcher._cache_total
        budget = max(1, int(hot_set_bytes * budget_frac))

        def serve(tag, enable):
            """Two fresh hosts serve `rounds` passes of the stream; in
            ownership mode each request is answered AS its host (the
            process-wide self_id flips — serial, so race-free)."""
            dbs = [mkdb(f"{tag}-h0", budget), mkdb(f"{tag}-h1", budget)]
            if enable:
                ownership.configure(enabled=True, members="h0,h1",
                                    self_id="h0", groups=32)
            else:
                ownership.OWNERSHIP.reset()
            h2d0 = obs.h2d_bytes.value()
            hit0 = obs.batch_cache_events.value(result="hit")
            miss0 = obs.batch_cache_events.value(result="miss")
            outs = []
            t0 = time.perf_counter()
            for _ in range(rounds):
                for i, db in enumerate(dbs):
                    if enable:
                        ownership.OWNERSHIP.self_id = f"h{i}"
                    outs.append(canon(db.search("bench", req).response()))
            wall = time.perf_counter() - t0
            hits = obs.batch_cache_events.value(result="hit") - hit0
            misses = obs.batch_cache_events.value(result="miss") - miss0
            stats = {
                "restage_bytes": int(obs.h2d_bytes.value() - h2d0),
                "hbm_hits": int(hits),
                "hbm_misses": int(misses),
                "hbm_hit_ratio": round(hits / max(1, hits + misses), 4),
                "wall_s": round(wall, 3),
            }
            ownership.OWNERSHIP.reset()
            return outs, stats

        off_outs, off = serve("off", enable=False)
        on_outs, on = serve("on", enable=True)
        identical = on_outs == off_outs
        assert identical, "ownership on/off responses diverged"
        assert on["restage_bytes"] < off["restage_bytes"], (
            f"owner routing re-staged {on['restage_bytes']} bytes, "
            f"independent caches {off['restage_bytes']} — the placement "
            "split saved nothing")
        assert on["hbm_hit_ratio"] >= off["hbm_hit_ratio"]

        # ---- hot-skew leg (ISSUE 18): heat-adaptive replication +
        # hedged dispatch vs plain rf=1 under an injected slow primary.
        # A zipf-ish stream sends ~80% of dispatches at ONE hot group
        # and ~20% at an alternate group with the same owner; the
        # primary's budget is 0.55x that two-group working set, so the
        # alternate traffic keeps thrashing the hot group out of HBM
        # and every hot re-stage pays the armed `h2d_delay`. With rf=2
        # the hot group heat-promotes, every hot dispatch hedges to the
        # replica host (full budget, hot-resident) after a fixed 25 ms
        # delay, and the hot-group p99 collapses from ~h2d_delay to
        # ~hedge delay — while every response stays byte-identical and
        # the replica stages ONLY promoted groups (duplicate-stage
        # bytes strictly bounded, residency accounting conserved).
        from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend
        from tempo_tpu.modules.querier import Querier
        from tempo_tpu.modules.ring import Ring
        from tempo_tpu.robustness import FAULTS

        n_samples = int(os.environ.get("BENCH_HEDGE_SAMPLES", 150))
        slow_s = float(os.environ.get("BENCH_HEDGE_H2D_DELAY_S", 0.12))
        hedge_ms = 25.0
        block_bytes = hot_set_bytes / n_blocks
        skew_budget = max(1, int(2 * block_bytes * budget_frac))

        def p99(xs):
            return sorted(xs)[min(len(xs) - 1, int(0.99 * len(xs)))]

        class _HostQuerier:
            """Serve AS one fleet member: identity is context-scoped
            (ownership.self_as), so concurrent hedged attempts on their
            daemon threads each see their own host, race-free."""

            def __init__(self, db, member):
                self.db = db
                self.member = member
                self.inner = Querier(db, Ring(), {})

            def search_blocks(self, breq):
                with ownership.self_as(self.member):
                    return self.inner.search_blocks(breq)

        def mk_breq(template):
            breq = tempopb.SearchBlocksRequest()
            breq.CopyFrom(template)
            breq.search_req.CopyFrom(req)
            breq.tenant_id = "bench"
            return breq

        def hedge_leg(tag, rf):
            db0 = mkdb(f"skew-{tag}-h0", skew_budget)  # primary: thrashes
            db1 = mkdb(f"skew-{tag}-h1", 64 << 30)     # replica: resident
            fe = QueryFrontend(
                [_HostQuerier(db0, "h0"), _HostQuerier(db1, "h1")],
                FrontendConfig(retries=3, target_bytes_per_job=1 << 30,
                               batch_jobs_per_request=1))
            # configure AFTER mkdb: TempoDB.__init__ applies its own
            # (disabled) ownership config
            ownership.configure(
                enabled=True, members="h0,h1", self_id="h0", groups=32,
                rf=rf, hot_rate=0.5, hedge_delay_ms=hedge_ms)
            by_block = {}
            for payload, template, owner, width in fe._search_batches("bench"):
                by_block[payload[0][0].block_id] = (
                    payload, template, owner, width)
            h0_blocks = [m.block_id for m in metas
                         if ownership.OWNERSHIP.owner_of(m.block_id) == "h0"]
            hot = h0_blocks[0]
            alt = next(b for b in h0_blocks[1:]
                       if (ownership.OWNERSHIP.group_of(b)
                           != ownership.OWNERSHIP.group_of(hot)))

            def dispatch(block_id):
                payload, template, owner, width = by_block[block_id]
                breq = mk_breq(template)
                t0 = time.perf_counter()
                r = fe._dispatch_batch(breq, owner, width, block_id)
                return time.perf_counter() - t0, canon(r)

            up0 = obs.hbm_replica_promotions.value(dir="up")
            hw0 = obs.hedged_dispatches.value(result="hedge_won")
            if rf > 1:
                # promote the hot group up front (the serving loop's
                # record_access gets there too — this pins the promoted
                # state for the whole measured stream) and pre-stage
                # the replica un-faulted so the first hedge never races
                # a cold staging put
                for _ in range(60):
                    ownership.OWNERSHIP.record_access(hot)
                assert ownership.OWNERSHIP.replica_indices(hot), \
                    "hot group failed to heat-promote"
                fe.queriers[1].search_blocks(mk_breq(by_block[hot][1]))
            # warm-up un-faulted: primary residency + kernel compile
            dispatch(hot)
            dispatch(alt)
            dispatch(hot)

            walls_hot, outs = [], []
            with FAULTS.armed("h2d_delay", delay_s=slow_s, count=10**6):
                for i in range(n_samples):
                    blk = alt if i % 5 == 4 else hot
                    w, out = dispatch(blk)
                    outs.append(out)
                    if blk is hot:
                        walls_hot.append(w)
            # residency accounting conserved on BOTH hosts: no negative
            # bytes, cache total == sum of its entries
            for db in (db0, db1):
                ent = sum(e.nbytes for e in db.batcher._cache.values())
                assert db.batcher._cache_total == ent >= 0, (
                    f"{tag}: cache accounting drifted "
                    f"({db.batcher._cache_total} != {ent})")
            stats = {
                "rf": rf,
                "hot_dispatches": len(walls_hot),
                "p50_s": round(sorted(walls_hot)[len(walls_hot) // 2], 4),
                "p99_s": round(p99(walls_hot), 4),
                "replica_staged_bytes": int(db1.batcher._cache_total),
                "promotions_up": int(
                    obs.hbm_replica_promotions.value(dir="up") - up0),
                "hedge_won": int(
                    obs.hedged_dispatches.value(result="hedge_won") - hw0),
            }
            ownership.OWNERSHIP.reset()
            return outs, stats

        rf1_outs, rf1 = hedge_leg("rf1", rf=1)
        rf2_outs, rf2 = hedge_leg("rf2", rf=2)
        assert rf1_outs == rf2_outs, (
            "hedged rf=2 responses diverged from rf=1")
        assert rf2["p99_s"] < rf1["p99_s"], (
            f"hedged rf=2 hot-group p99 {rf2['p99_s']}s did not beat "
            f"rf=1 {rf1['p99_s']}s under a {slow_s}s slow primary")
        # rf=1 never touches the second host; rf=2 replicates ONLY the
        # promoted group(s) — hot plus at most the alternate if its
        # in-stream rate crossed the threshold — never the whole
        # blocklist (24 blocks) the primary carries
        assert rf1["replica_staged_bytes"] == 0, (
            "rf=1 leg staged bytes on the non-owner host")
        assert rf2["replica_staged_bytes"] <= 2.5 * block_bytes, (
            f"replica staged {rf2['replica_staged_bytes']} bytes — more "
            f"than the promoted groups (block ~{int(block_bytes)} bytes)")
        assert rf2["hedge_won"] >= 1, "no hedge ever won against the slow primary"
        assert rf2["promotions_up"] >= 1 and rf1["promotions_up"] == 0
        hot_skew = {
            "samples": n_samples,
            "h2d_delay_s": slow_s,
            "hedge_delay_ms": hedge_ms,
            "skew_budget_bytes": int(skew_budget),
            "byte_identical": rf1_outs == rf2_outs,
            "rf1": rf1,
            "rf2": rf2,
            "p99_speedup": round(rf1["p99_s"] / max(rf2["p99_s"], 1e-9), 2),
        }

        return {
            "blocks": n_blocks,
            "rounds": rounds,
            "hosts": 2,
            "hot_set_bytes": int(hot_set_bytes),
            "hbm_budget_bytes": int(budget),
            "byte_identical": identical,
            "ownership_off": off,
            "ownership_on": on,
            "restage_bytes_saved": off["restage_bytes"] - on["restage_bytes"],
            "owner_routed": int(obs.hbm_owner_routed.value(route="owner")),
            "non_owner_host_routed": int(
                obs.hbm_owner_routed.value(route="non_owner_host")),
            "hot_skew": hot_skew,
        }


def phase_packing():
    """Packed HBM residency contract (docs/search-packed-residency.md,
    ISSUE 13 acceptance): over a mixed-cardinality tag-heavy corpus,

      - `search_packed_residency: true` stages STRICTLY fewer physical
        HBM bytes than false (target >= 40% fewer on this corpus);
      - responses are byte-identical packed on vs off;
      - at a FIXED HBM budget sized below the unpacked hot set, the
        packed layout keeps more batches resident and serves a higher
        HBM hit ratio — the bytes saved become residency;
      - scan throughput is recorded for both (asserted no worse than a
        conservative noise floor on shared-CPU hosts; the exact ratio
        ships in detail.packing).

    Runs on whatever backend jax resolves; the final doc names it.
    """
    import json as _json
    import tempfile

    from tempo_tpu import tempopb
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.backend.types import (
        BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER,
    )
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.encoding.v2.compression import compress
    from tempo_tpu.observability import metrics as obs
    from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
    from tempo_tpu.search.data import SearchData

    n_blocks = int(os.environ.get("BENCH_PACKING_BLOCKS", 18))
    entries_per_block = int(os.environ.get("BENCH_PACKING_ENTRIES", 4096))
    rounds = int(os.environ.get("BENCH_PACKING_ROUNDS", 4))
    budget_frac = float(os.environ.get("BENCH_PACKING_BUDGET_FRAC", 0.55))

    def mk_block(s):
        """Tag-heavy entries (kv is ~70% of a batch's bytes) cycling
        the width classes the planner picks per block union: tiny
        dictionaries (≤15 values → 4-bit codes vs the legacy int8),
        ~240-value dictionaries (uint8 codes vs int16 — the ISSUE's
        '200 distinct values' case), and the same with durations past
        the uint16 boundary so the quantized+residual path runs for
        real. Per-NAMESPACE cardinality: the width is chosen from the
        block's value-dictionary UNION across its 12 tag namespaces."""
        rng = np.random.default_rng(1000 + s)
        card = [1, 20, 20][s % 3]      # union: 12 / ~240 / ~240 values
        dur_max = [40_000, 60_000, 1 << 20][s % 3]
        entries = []
        for i in range(entries_per_block):
            sd = SearchData(
                trace_id=rng.bytes(16),
                start_s=int(rng.integers(1, 5_000)),
                end_s=int(rng.integers(5_000, 10_000)),
                dur_ms=int(rng.integers(0, dur_max)),
            )
            sd.kvs = {"service.name":
                      {f"svc-{int(rng.integers(0, card)):05d}"}}
            for t in range(11):
                sd.kvs[f"tag{t:02d}"] = {
                    f"t{t}-{int(rng.integers(0, card)):05d}"}
            entries.append(sd)
        return ColumnarPages.build(entries, PageGeometry(256, 16))

    def canon(resp):
        r = tempopb.SearchResponse()
        r.CopyFrom(resp)
        r.metrics.device_seconds = 0.0
        r.metrics.inspected_bytes_device = 0
        return r.SerializeToString()

    with tempfile.TemporaryDirectory() as td:
        be = LocalBackend(td + "/blocks")
        metas = []
        for s in range(n_blocks):
            pages = mk_block(s)
            m = BlockMeta(tenant_id="bench", encoding="none")
            blob = compress(pages.to_bytes(), "none")
            hdr = dict(pages.header)
            hdr["encoding"] = "none"
            hdr["compressed_size"] = len(blob)
            be.write("bench", m.block_id, NAME_SEARCH, blob)
            be.write("bench", m.block_id, NAME_SEARCH_HEADER,
                     _json.dumps(hdr).encode())
            metas.append(m)

        # limits sized above any possible match count: under a tight
        # budget the two layouts cache (and therefore order) groups
        # differently, and an early-quit freezes whichever subset
        # happened to finish first — the documented residency-order
        # tradeoff, not a packing property
        reqs = []
        for i in range(6):
            r = tempopb.SearchRequest()
            r.tags[f"tag{i:02d}"] = f"t{i}-000"
            r.limit = 200_000
            reqs.append(r)
        edge = 1 << 5  # q-bucket edge at the 2^20 duration class
        r = tempopb.SearchRequest()
        r.min_duration_ms = 3 * edge
        r.max_duration_ms = 1 << 18
        r.limit = 200_000
        reqs.append(r)

        def mkdb(tag, enabled, budget):
            # one 16-page block per staged group: widths are a
            # per-batch property (the max over member blocks), so
            # homogeneous groups let every cardinality class keep its
            # own narrowest width — the production analog is tenants
            # whose dictionary shape is uniform within a group
            db = TempoDB(be, f"{td}/wal-{tag}", TempoDBConfig(
                auto_mesh=False, host_state_dir="",
                search_max_batch_pages=16,
                search_batch_cache_bytes=budget,
                search_coalesce_max_queries=0,
                search_packed_residency=enabled))
            db.blocklist.update("bench", add=metas)
            return db

        def serve(tag, enabled, budget):
            db = mkdb(tag, enabled, budget)
            hit0 = obs.batch_cache_events.value(result="hit")
            miss0 = obs.batch_cache_events.value(result="miss")
            h2d0 = obs.h2d_bytes.value()
            outs = []
            traces = 0
            t0 = time.perf_counter()
            for _ in range(rounds):
                for req in reqs:
                    res = db.search("bench", req)
                    traces += int(res.metrics.inspected_traces)
                    outs.append(canon(res.response()))
            wall = time.perf_counter() - t0
            hits = obs.batch_cache_events.value(result="hit") - hit0
            misses = obs.batch_cache_events.value(result="miss") - miss0
            stats = {
                "physical_bytes": int(db.batcher._cache_total),
                "logical_bytes": int(db.batcher._cache_logical),
                "resident_batches": len(db.batcher._cache),
                "restage_bytes": int(obs.h2d_bytes.value() - h2d0),
                "hbm_hits": int(hits),
                "hbm_misses": int(misses),
                "hbm_hit_ratio": round(hits / max(1, hits + misses), 4),
                "wall_s": round(wall, 3),
                "traces_per_s": round(traces / max(wall, 1e-9)),
            }
            return outs, stats

        # unbudgeted pass: the pure physical-bytes + byte-identity claim
        off_outs, off = serve("off", False, 64 << 30)
        on_outs, on = serve("on", True, 64 << 30)
        assert on_outs == off_outs, "packed on/off responses diverged"
        assert on["physical_bytes"] < off["physical_bytes"], (
            "packing saved no staged bytes")
        saved = 1 - on["physical_bytes"] / max(1, off["physical_bytes"])
        # acceptance target is >= 40% on this corpus; assert a hard
        # floor with margin for geometry padding drift
        assert saved >= 0.35, f"only {saved:.1%} physical bytes saved"
        # the logical (unpacked-equivalent) view is layout-independent
        # (budget totals additionally carry per-predicate query-table
        # bytes, which the logical split leaves out)
        assert on["logical_bytes"] == off["logical_bytes"]
        # throughput: no worse, within the shared-CPU noise floor
        # (exact ratio recorded either way)
        tput_ratio = on["traces_per_s"] / max(1, off["traces_per_s"])
        assert tput_ratio >= 0.7, (
            f"packed scan throughput regressed to {tput_ratio:.2f}x")

        # fixed-budget pass: bytes saved become residency — budget sized
        # below the unpacked hot set, so unpacked thrashes where packed
        # stays resident
        budget = max(1, int(off["physical_bytes"] * budget_frac))
        boff_outs, boff = serve("boff", False, budget)
        bon_outs, bon = serve("bon", True, budget)
        assert bon_outs == boff_outs
        assert bon["resident_batches"] >= boff["resident_batches"]
        assert bon["hbm_hit_ratio"] >= boff["hbm_hit_ratio"]

        return {
            "blocks": n_blocks,
            "entries_per_block": entries_per_block,
            "rounds": rounds,
            "physical_bytes_saved_ratio": round(saved, 4),
            "throughput_ratio_on_vs_off": round(tput_ratio, 3),
            "byte_identical": True,
            "packing_off": off,
            "packing_on": on,
            "fixed_budget_bytes": int(budget),
            "fixed_budget_off": boff,
            "fixed_budget_on": bon,
        }


def phase_structural():
    """Structural query engine contract (ISSUE 14,
    docs/search-structural-queries.md): a parent/child + descendant +
    aggregate query mix over a span-bearing corpus, asserting

      - byte-identity: the compiled device path's match set equals the
        host reference evaluator's (structural.eval_host), per query;
      - a throughput floor vs the equivalent POST-FILTER baseline (the
        pre-structural architecture: run the legacy scan, fetch, then
        evaluate the structural predicate per trace on host) — the
        compiled path must not lose to interpreting the tree per row;
      - the compiled plan tree with per-node device-seconds lands in
        this phase's detail (the ?explain=1 surface).
    """
    import tempfile

    from tempo_tpu import tempopb
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.search import ir, structural
    from tempo_tpu.search.columnar import PageGeometry
    from tempo_tpu.search.data import (SearchData, SpanData,
                                       encode_search_data)

    n_blocks = int(os.environ.get("BENCH_STRUCTURAL_BLOCKS", 6))
    entries_per_block = int(os.environ.get("BENCH_STRUCTURAL_ENTRIES",
                                           4096))
    rounds = int(os.environ.get("BENCH_STRUCTURAL_ROUNDS", 3))
    svcs = [f"svc-{i:02d}" for i in range(12)]

    def mk_entries(s):
        rng = np.random.default_rng(2000 + s)
        out = []
        for i in range(entries_per_block):
            sd = SearchData(
                trace_id=rng.bytes(16),
                start_s=int(rng.integers(1, 5_000)),
                end_s=int(rng.integers(5_000, 10_000)),
                dur_ms=int(rng.integers(1, 30_000)),
            )
            svc = svcs[int(rng.integers(0, len(svcs)))]
            sd.kvs = {"service.name": {svc},
                      "env": {"prod" if i % 2 else "dev"}}
            n_sp = int(rng.integers(1, 8))
            for j in range(n_sp):
                sd.spans.append(SpanData(
                    parent=(-1 if j == 0 else int(rng.integers(0, j))),
                    dur_ms=int(rng.integers(1, 2_000)),
                    kind=int(rng.integers(0, 6)),
                    kvs={"service.name":
                         {svcs[int(rng.integers(0, len(svcs)))]},
                         "name": {f"op{int(rng.integers(0, 4))}"}}))
            out.append(sd)
        return out

    queries = {
        "parent_child": ir.parse(
            '{"child": {"parent": {"tag": {"k": "service.name",'
            ' "v": "svc-03"}}, "child": {"dur": {"min_ms": 500}}}}'),
        "descendant": ir.parse(
            '{"desc": {"anc": {"kind": "server"},'
            ' "span": {"tag": {"k": "name", "v": "op1"}}}}'),
        "count": ir.parse(
            '{"count": {"of": {"dur": {"min_ms": 1000}},'
            ' "op": ">", "n": 2}}'),
        "quantile": ir.parse(
            '{"quantile": {"of": {"tag": {"k": "name", "v": "op"}},'
            ' "q": "0.9", "op": ">=", "ms": 1200}}'),
    }

    with tempfile.TemporaryDirectory() as td:
        be = LocalBackend(td + "/blocks")
        db = TempoDB(be, td + "/wal", TempoDBConfig(
            auto_mesh=False, search_structural_enabled=True,
            search_geometry=PageGeometry(256, 8)))
        corpus = []
        for s in range(n_blocks):
            entries = sorted(mk_entries(s), key=lambda sd: sd.trace_id)
            corpus.extend(entries)
            db.write_block_direct(
                "bench",
                [(sd.trace_id, encode_search_data(sd), sd.start_s,
                  sd.end_s) for sd in entries],
                search_entries=entries)

        total = len(corpus)
        results = {}
        compiled_wall = 0.0
        for name, expr in queries.items():
            want = {sd.trace_id for sd in corpus
                    if structural.eval_host(expr, sd)}
            req = tempopb.SearchRequest()
            req.limit = total
            structural.attach_query(req, expr)
            # warm (stage + compile), then measure
            db.search("bench", req)
            t0 = time.perf_counter()
            for _ in range(rounds):
                res = db.search("bench", req)
            wall = (time.perf_counter() - t0) / rounds
            compiled_wall += wall
            got = {bytes.fromhex(m.trace_id)
                   for m in res.response().traces}
            assert got == want, (
                f"{name}: compiled match set diverged from the host "
                f"reference ({len(got)} vs {len(want)})")
            # post-filter-on-host baseline: the legacy scan already ran
            # once above; the honest extra cost of the old architecture
            # is interpreting the structural tree per fetched trace
            t0 = time.perf_counter()
            n_match = sum(1 for sd in corpus
                          if structural.eval_host(expr, sd))
            base_wall = time.perf_counter() - t0
            results[name] = {
                "matches": len(want),
                "compiled_ms": round(wall * 1e3, 3),
                "post_filter_baseline_ms": round(base_wall * 1e3, 3),
                "speedup_vs_post_filter": round(base_wall / max(wall,
                                                                1e-9), 2),
            }
            _ = n_match

        # throughput floor: the compiled mix must beat interpreting the
        # tree per row (generous floor for shared-CPU noise)
        base_total = sum(r["post_filter_baseline_ms"]
                         for r in results.values()) / 1e3
        assert compiled_wall <= base_total / 0.5, (
            f"compiled structural mix ({compiled_wall:.3f}s) lost to the "
            f"post-filter baseline ({base_total:.3f}s) by >2x")

        # explain surface: per-node device-seconds in the plan tree
        req = tempopb.SearchRequest()
        req.limit = 10
        req.explain = True
        structural.attach_query(req, queries["parent_child"])
        stats = json.loads(
            db.search("bench", req).response().metrics.query_stats_json)
        nodes = stats["structural"]["nodes"]
        assert nodes and all("device_ms" in n for n in nodes)

        concurrency = _structural_concurrency_subphase(td, mk_entries)
        mixed = _structural_mixed_subphase(td, mk_entries)
        sharded_leg = _structural_sharded_span_leg(mk_entries)
        remainder_leg = _structural_remainder_leg(mk_entries)

        return {
            "blocks": n_blocks,
            "entries_per_block": entries_per_block,
            "total_traces": total,
            "byte_identical": True,
            "compiled_mix_traces_per_s": round(
                total * len(queries) / max(compiled_wall, 1e-9)),
            "post_filter_traces_per_s": round(
                total * len(queries) / max(base_total, 1e-9)),
            "queries": results,
            "explain_plan_nodes": nodes,
            "structural_concurrency": concurrency,
            "structural_mixed": mixed,
            "mesh_sharded_spans": sharded_leg,
            "mesh_remainder_pages": remainder_leg,
        }


def _structural_concurrency_subphase(td, mk_entries):
    """`structural_concurrency` sub-phase (ISSUE 15): a barrier-synced
    8-way SAME-PLAN-SHAPE structural load against the serving path with
    plan-shape stacking on. Asserts the fused dispatches per request
    land well below 1 (>= 2x fewer kernel launches than the solo-flush
    behavior) and that every concurrent response is byte-identical to
    the same query run serially."""
    import threading

    from tempo_tpu import tempopb
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.search import ir, structural
    from tempo_tpu.search.columnar import PageGeometry
    from tempo_tpu.search.data import encode_search_data

    be = LocalBackend(td + "/blocks-conc")
    db = TempoDB(be, td + "/wal-conc", TempoDBConfig(
        auto_mesh=False, search_structural_enabled=True,
        search_structural_stack_enabled=True,
        search_coalesce_window_s=0.05,
        search_geometry=PageGeometry(256, 8)))
    corpus = []
    for s in range(2):
        entries = sorted(mk_entries(s), key=lambda sd: sd.trace_id)
        corpus.extend(entries)
        db.write_block_direct(
            "bench",
            [(sd.trace_id, encode_search_data(sd), sd.start_s, sd.end_s)
             for sd in entries],
            search_entries=entries)
    N = 8
    exprs = [ir.parse(
        '{"child": {"parent": {"tag": {"k": "service.name",'
        ' "v": "svc-%02d"}}, "child": {"dur": {"min_ms": %d}}}}'
        % (i % 12, 100 * (i + 1))) for i in range(N)]

    def search_one(expr):
        req = tempopb.SearchRequest()
        req.limit = len(corpus)
        structural.attach_query(req, expr)
        resp = db.search("bench", req).response()
        return sorted(m.trace_id for m in resp.traces), \
            int(resp.metrics.inspected_traces)

    serial = [search_one(e) for e in exprs]   # also warms stage+compile
    co = db.batcher.coalescer
    d0, q0 = co.dispatches, co.queries
    out = [None] * N
    barrier = threading.Barrier(N)

    def one(i):
        barrier.wait()
        out[i] = search_one(exprs[i])

    t0 = time.perf_counter()
    threads = [threading.Thread(target=one, args=(i,)) for i in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    for i in range(N):
        assert out[i] == serial[i], f"query {i} diverged under stacking"
    dispatches = co.dispatches - d0
    served = co.queries - q0
    assert served == N
    per_request = dispatches / N
    # the acceptance floor: >= 2x fewer launches than solo (which costs
    # one dispatch per request)
    assert per_request <= 0.5, (
        f"stacking fused too little: {dispatches} dispatches for {N} "
        "same-plan requests")
    return {
        "requests": N,
        "dispatches": dispatches,
        "dispatches_per_request": round(per_request, 3),
        "stacked_queries": co.structural_stacked,
        "stack_ratio": co.stats()["structural_stack_ratio"],
        "byte_identical_vs_serial": True,
        "wall_ms": round(wall * 1e3, 3),
    }


def _structural_mixed_subphase(td, mk_entries):
    """`structural_mixed` sub-phase (ISSUE 16): a barrier-synced 8-way
    MIXED-plan structural load (>= 3 distinct plan shapes that
    canonicalize into one bucket) against the serving path with
    shape-bucketed stacking on. Asserts the bucketed dispatches per
    request land at or below 0.5 (>= 2x fewer launches than the
    per-plan flush the exact-plan grouping costs), byte-identity vs the
    same queries run serially, and cost-apportionment conservation —
    the members' attributed device seconds sum to the fused dispatch
    records' totals."""
    import threading

    from tempo_tpu import tempopb
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.observability.profile import PROFILER
    from tempo_tpu.search import ir, structural
    from tempo_tpu.search.columnar import PageGeometry
    from tempo_tpu.search.data import encode_search_data

    be = LocalBackend(td + "/blocks-mixed")
    db = TempoDB(be, td + "/wal-mixed", TempoDBConfig(
        auto_mesh=False, search_structural_enabled=True,
        search_structural_stack_enabled=True,
        search_structural_bucket_enabled=True,
        search_coalesce_window_s=0.05,
        search_geometry=PageGeometry(256, 8)))
    corpus = []
    for s in range(2):
        entries = sorted(mk_entries(s), key=lambda sd: sd.trace_id)
        corpus.extend(entries)
        db.write_block_direct(
            "bench",
            [(sd.trace_id, encode_search_data(sd), sd.start_s, sd.end_s)
             for sd in entries],
            search_entries=entries)
    # three DISTINCT plan shapes, one canonical bucket (3 span slots +
    # exists+root -> NS 4 / NT 2 / relational): the mixed dashboard
    # traffic exact-plan grouping cannot fuse
    shapes = [
        lambda i: (
            '{"child": {"parent": {"tag": {"k": "service.name",'
            ' "v": "svc-%02d"}}, "child": {"dur": {"min_ms": %d}}}}'
            % (i % 12, 100 * (i + 1))),
        lambda i: (
            '{"child": {"parent": {"tag": {"k": "service.name",'
            ' "v": "svc-%02d"}}, "child": {"kind": "server"}}}'
            % (i % 12)),
        lambda i: (
            '{"child": {"parent": {"dur": {"min_ms": %d}},'
            ' "child": {"tag": {"k": "name", "v": "op1"}}}}'
            % (100 * (i + 1))),
    ]
    N = 8
    exprs = [ir.parse(shapes[i % 3](i)) for i in range(N)]
    n_plans = len({str(e) for e in exprs})
    assert n_plans >= 3

    def search_one(expr):
        req = tempopb.SearchRequest()
        req.limit = len(corpus)
        structural.attach_query(req, expr)
        resp = db.search("bench", req).response()
        return sorted(m.trace_id for m in resp.traces), \
            int(resp.metrics.inspected_traces)

    serial = [search_one(e) for e in exprs]   # also warms stage+compile
    co = db.batcher.coalescer
    d0, q0, b0 = co.dispatches, co.queries, co.structural_bucketed
    out = [None] * N
    barrier = threading.Barrier(N)

    def one(i):
        barrier.wait()
        out[i] = search_one(exprs[i])

    t0 = time.perf_counter()
    threads = [threading.Thread(target=one, args=(i,))
               for i in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    for i in range(N):
        assert out[i] == serial[i], \
            f"query {i} diverged under bucketed stacking"
    dispatches = co.dispatches - d0
    served = co.queries - q0
    assert served == N
    per_request = dispatches / N
    # the acceptance floor: >= 2x fewer launches than the per-plan
    # flush (which costs one dispatch per request here — every window
    # holds mixed plans)
    assert per_request <= 0.5, (
        f"bucketing fused too little: {dispatches} dispatches for {N} "
        f"mixed-plan requests across {n_plans} shapes")
    assert co.structural_bucketed - b0 > 0, "no bucketed fusion booked"
    conserved = _mixed_conservation_leg(mk_entries, exprs)
    stats = co.stats()
    return {
        "requests": N,
        "plan_shapes": n_plans,
        "dispatches": dispatches,
        "dispatches_per_request": round(per_request, 3),
        "bucketed_queries": co.structural_bucketed - b0,
        "bucket_occupancy": {
            bk: row["occupancy"]
            for bk, row in stats.get("buckets", {}).items()},
        "byte_identical_vs_serial": True,
        "cost_conserved": conserved,
        "wall_ms": round(wall * 1e3, 3),
    }


def _mixed_conservation_leg(mk_entries, exprs):
    """Cost-apportionment conservation for a bucketed MIXED-plan fused
    dispatch: exactly one size-flushed group through the coalescer, and
    per dispatch stage the members' attributed shares sum to the fused
    record's totals to the float bit (query_stats.apportion weights by
    each member's ACTIVE node tables — pad slots are never billed)."""
    import threading

    from tempo_tpu import tempopb
    from tempo_tpu.observability.profile import PROFILER
    from tempo_tpu.search import query_stats, structural
    from tempo_tpu.search.batcher import QueryCoalescer
    from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
    from tempo_tpu.search.engine import resolve_top_k
    from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi
    from tempo_tpu.search.structural import compile_structural

    N = len(exprs)
    blocks = [ColumnarPages.build(
        sorted(mk_entries(9), key=lambda sd: sd.trace_id),
        PageGeometry(256, 8))]
    eng = MultiBlockEngine(top_k=256)
    batch = eng.stage(blocks)
    co = QueryCoalescer(eng, window_s=60.0, max_queries=N,
                        active_fn=lambda: N)
    mqs = []
    for e in exprs:
        req = tempopb.SearchRequest()
        req.limit = 256
        structural.attach_query(req, e)
        mq = compile_multi(blocks, req, cache_on=batch)
        mq.structural = compile_structural(
            e, blocks, cache_on=batch, staged_dicts=batch.staged_dicts)
        mqs.append(mq)
    stats = [query_stats.QueryStats("bench") for _ in range(N)]
    futs = [None] * N
    caught: list[dict] = []
    listener = caught.append

    def submit(i):
        with query_stats.activate(stats[i]):
            futs[i] = co.submit(batch, mqs[i],
                                resolve_top_k(eng.top_k, mqs[i].limit),
                                peers=N)

    PROFILER.add_listener(listener)
    try:
        threads = [threading.Thread(target=submit, args=(i,))
                   for i in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for f in futs:
            f.result(timeout=120)
    finally:
        PROFILER._listeners.remove(listener)
    assert co.queries == N and co.dispatches == 1, (
        f"mixed group did not size-flush as ONE bucketed dispatch "
        f"({co.dispatches} dispatches)")
    fused = [rd for rd in caught if rd.get("mode") == "coalesced"]
    assert len(fused) == 1
    totals = {k: v / 1e3 for k, v in fused[0]["stages_ms"].items()}
    for stage, total in totals.items():
        attributed = sum(qs.device_stages.get(stage, 0.0)
                         for qs in stats)
        assert abs(attributed - total) <= 1e-12 * max(1.0, total), (
            f"stage {stage!r}: apportioned {attributed!r}s does not "
            f"conserve the dispatch total {total!r}s")
    return True


def _structural_remainder_leg(mk_entries):
    """Mesh remainder-shard leg of the `structural` phase (ISSUE 16):
    stage a NON-multiple page count over the mesh with the pow2 vs the
    minimal-multiple (remainder-shard) layout, report the staged-byte
    reduction, and assert byte-identical answers through the dist
    kernels both ways."""
    import jax

    from tempo_tpu import tempopb
    from tempo_tpu.search import ir, structural
    from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
    from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi
    from tempo_tpu.search.structural import STRUCTURAL, compile_structural

    if len(jax.devices()) < 2:
        return {"skipped": "single device — no mesh to shard over"}
    from tempo_tpu.parallel import make_mesh

    mesh = make_mesh()
    n_sh = int(mesh.devices.size)
    geo = PageGeometry(256, 8)
    blocks = [ColumnarPages.build(
        sorted(mk_entries(s), key=lambda sd: sd.trace_id), geo)
        for s in range(2)]
    # append one-page blocks until the page total is ragged enough that
    # the minimal shard multiple actually beats the pow2 layout (the
    # measured-saving contract must hold at any corpus-size override)
    pool: list = []
    pool_seed = 2

    def minimal_vs_pow2(total):
        m = max(n_sh, -(-total // n_sh) * n_sh)
        p = max(n_sh, 1)
        while p < total:
            p *= 2
        return m, p

    while True:
        total_pages = sum(b.n_pages for b in blocks)
        m, p = minimal_vs_pow2(total_pages)
        if m < p:
            break
        while len(pool) < geo.entries_per_page:
            pool.extend(sorted(mk_entries(pool_seed),
                               key=lambda sd: sd.trace_id))
            pool_seed += 1
        blocks.append(ColumnarPages.build(
            pool[:geo.entries_per_page], geo))
        del pool[:geo.entries_per_page]
    expr = ir.parse(
        '{"child": {"parent": {"tag": {"k": "service.name",'
        ' "v": "svc-03"}}, "child": {"dur": {"min_ms": 500}}}}')

    def run(remainder: bool):
        prev = STRUCTURAL.remainder_pages
        STRUCTURAL.remainder_pages = remainder
        try:
            eng = MultiBlockEngine(top_k=4096, mesh=mesh)
            batch = eng.stage(blocks)
            req = tempopb.SearchRequest()
            req.limit = 4096
            structural.attach_query(req, expr)
            mq = compile_multi(blocks, req, cache_on=batch)
            mq.structural = compile_structural(
                expr, blocks, cache_on=batch,
                staged_dicts=batch.staged_dicts)
            count, _ins, scores, idx = eng.scan(batch, mq)
            got = frozenset(
                (int(s), int(i))
                for s, i in zip(scores.tolist(), idx.tolist()) if s >= 0)
            pages = int(batch.device["kv_key"].shape[0])
            return count, got, pages, int(batch.device_nbytes)
        finally:
            STRUCTURAL.remainder_pages = prev

    p_count, p_got, p_pages, p_bytes = run(False)
    r_count, r_got, r_pages, r_bytes = run(True)
    assert (p_count, p_got) == (r_count, r_got), \
        "remainder-shard layout diverged from the pow2 layout"
    assert r_pages < p_pages, (
        f"remainder layout saved nothing: {r_pages} vs {p_pages} staged "
        f"pages for {total_pages} real pages on {n_sh} shards")
    return {
        "shards": n_sh,
        "real_pages": total_pages,
        "pow2_staged_pages": p_pages,
        "remainder_staged_pages": r_pages,
        "pow2_staged_bytes": p_bytes,
        "remainder_staged_bytes": r_bytes,
        "staged_byte_ratio": round(r_bytes / max(1, p_bytes), 3),
        "byte_identical": True,
        "matches": int(p_count),
    }


def _structural_sharded_span_leg(mk_entries):
    """Mesh-sharded-span leg of the `structural` phase (ISSUE 15):
    stage one span-bearing batch over the mesh with the replicated vs
    the segment-aligned sharded layout, report per-shard span bytes
    (sharded ~ 1/P of replicated), and assert byte-identical answers
    through the dist kernel both ways."""
    import jax

    from tempo_tpu import tempopb
    from tempo_tpu.search import ir, structural
    from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
    from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi
    from tempo_tpu.search.structural import STRUCTURAL, compile_structural

    if len(jax.devices()) < 2:
        return {"skipped": "single device — no mesh to shard over"}
    from tempo_tpu.parallel import make_mesh

    mesh = make_mesh()
    n_sh = int(mesh.devices.size)
    geo = PageGeometry(256, 8)
    blocks = [ColumnarPages.build(
        sorted(mk_entries(s), key=lambda sd: sd.trace_id), geo)
        for s in range(2)]
    expr = ir.parse(
        '{"child": {"parent": {"tag": {"k": "service.name",'
        ' "v": "svc-03"}}, "child": {"dur": {"min_ms": 500}}}}')

    def run(shard: bool):
        prev = STRUCTURAL.shard_spans
        STRUCTURAL.shard_spans = shard
        try:
            eng = MultiBlockEngine(top_k=4096, mesh=mesh)
            batch = eng.stage(blocks)
            req = tempopb.SearchRequest()
            req.limit = 4096
            structural.attach_query(req, expr)
            mq = compile_multi(blocks, req, cache_on=batch)
            mq.structural = compile_structural(
                expr, blocks, cache_on=batch,
                staged_dicts=batch.staged_dicts)
            count, _ins, scores, idx = eng.scan(batch, mq)
            got = frozenset(
                (int(s), int(i))
                for s, i in zip(scores.tolist(), idx.tolist()) if s >= 0)
            span_total = sum(int(a.nbytes)
                             for a in batch.span_device.values())
            # replicated layout pins the FULL segment on every shard;
            # the sharded layout splits its global arrays 1/P each
            per_shard = (span_total // n_sh) if batch.span_sharded \
                else span_total
            assert batch.span_sharded == shard
            return count, got, per_shard
        finally:
            STRUCTURAL.shard_spans = prev

    rep_count, rep_got, rep_bytes = run(False)
    sh_count, sh_got, sh_bytes = run(True)
    assert (rep_count, rep_got) == (sh_count, sh_got), \
        "sharded span layout diverged from replicated"
    return {
        "shards": n_sh,
        "replicated_span_bytes_per_shard": rep_bytes,
        "sharded_span_bytes_per_shard": sh_bytes,
        "span_hbm_ratio": round(sh_bytes / max(1, rep_bytes), 3),
        "byte_identical": True,
        "matches": int(rep_count),
    }


def phase_analytics():
    """Device-side aggregate analytics contract (ISSUE 19,
    docs/search-analytics.md):

      - ingest: a paired native-summary corpus (client + server rows of
        each edge in the same push, unique span ids) through the batched
        device reduction vs the per-span Python walk — the registries
        must come out BYTE-identical (exposition, LRU order, pairing
        store) and the batched path >= 5x the walk's rows/s (hard floor
        below the target for shared-CPU noise; exact ratio recorded);
      - query: ?agg=red answers over the serving path must equal a
        plain-python reference aggregator exactly, and the aggregate's
        marginal cost vs the same queries without ?agg= is recorded.

    Runs with the gate flipped per leg; the standard `_breaker` /
    `device_wedged` riders label any mid-run trip.
    """
    import bisect as _bisect
    import struct as _struct
    import tempfile

    from tempo_tpu import tempopb
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.modules.generator import (MetricsGenerator,
                                             ServiceGraphProcessor,
                                             SpanMetricsProcessor)
    from tempo_tpu.search.analytics import ANALYTICS, MS_BUCKETS, attach_agg
    from tempo_tpu.search.data import SearchData, encode_search_data

    n_rows = int(os.environ.get("BENCH_ANALYTICS_ROWS", 8192))
    n_pushes = int(os.environ.get("BENCH_ANALYTICS_PUSHES", 10))
    floor = float(os.environ.get("BENCH_ANALYTICS_FLOOR", 4.0))
    q_entries = int(os.environ.get("BENCH_ANALYTICS_ENTRIES", 4096))
    q_rounds = int(os.environ.get("BENCH_ANALYTICS_ROUNDS", 3))

    # ---- ingest leg -------------------------------------------------
    _ROW = _struct.Struct("<6IQQ8s8s")
    svcs = [f"svc-{i:02d}" for i in range(8)]
    ops = [f"op-{i}" for i in range(4)]
    strs = svcs + ops

    def mk_push(seed):
        """client+server rows of each edge in ONE push, globally unique
        span ids — every pair completes in-batch, the walk's hot path."""
        rng = np.random.default_rng(3000 + seed)
        tids = [rng.bytes(16) for _ in range(256)]
        rows = []
        sid = seed * n_rows + 1
        for _ in range(n_rows // 2):
            ti = int(rng.integers(0, len(tids)))
            name = len(svcs) + int(rng.integers(0, len(ops)))
            start = int(rng.integers(0, 1 << 40))
            dur = int(rng.integers(1, 20_000_000_000))
            csid = sid.to_bytes(8, "little")
            ssid = (sid + 1).to_bytes(8, "little")
            sid += 2
            rows.append((ti, int(rng.integers(0, len(svcs))), name, 3,
                         2 * int(rng.integers(0, 2)), 0, start,
                         start + dur, csid, b"\x00" * 8))
            rows.append((ti, int(rng.integers(0, len(svcs))),
                         len(svcs) + int(rng.integers(0, len(ops))), 2,
                         2 * int(rng.integers(0, 2)), 0, start,
                         start + dur // 2, ssid, csid))
        out = [_struct.pack("<I", len(strs))]
        for s in strs:
            b = s.encode()
            out.append(_struct.pack("<H", len(b)))
            out.append(b)
        out.append(_struct.pack("<I", len(rows)))
        for r in rows:
            out.append(_ROW.pack(*r))
        return b"".join(out), tids

    pushes = [mk_push(s) for s in range(n_pushes)]

    def feed(enabled):
        ANALYTICS.configure(enabled=enabled, min_rows=1)
        if enabled:  # compile warm-up outside the measurement
            scratch = MetricsGenerator()
            scratch.push_summary_blob("warm", *pushes[0])
        gen = MetricsGenerator()
        t0 = time.perf_counter()
        for blob, tids in pushes:
            gen.push_summary_blob("bench", blob, tids)
        wall = time.perf_counter() - t0
        _reg, procs = gen._instance("bench")
        spm = next(p for p in procs
                   if isinstance(p, SpanMetricsProcessor))
        sgp = next(p for p in procs
                   if isinstance(p, ServiceGraphProcessor))
        snap = (gen.collect("bench"), list(spm._series),
                {k: v[:3] for k, v in sgp._store.items()})
        return wall, snap

    walk_wall, walk_snap = feed(False)
    dev_wall, dev_snap = feed(True)
    ANALYTICS.configure(enabled=False)
    assert dev_snap == walk_snap, (
        "batched ingest registries diverged from the per-span walk")
    speedup = walk_wall / max(dev_wall, 1e-9)
    total_rows = n_rows * n_pushes
    assert speedup >= floor, (
        f"batched ingest only {speedup:.2f}x the walk "
        f"(target 5x, floor {floor}x)")

    ingest = {
        "rows_per_push": n_rows,
        "pushes": n_pushes,
        "walk_rows_per_s": round(total_rows / max(walk_wall, 1e-9)),
        "device_rows_per_s": round(total_rows / max(dev_wall, 1e-9)),
        "speedup": round(speedup, 2),
        "byte_identical": True,
    }

    # ---- query leg --------------------------------------------------
    def mk_entries(s):
        rng = np.random.default_rng(4000 + s)
        out = []
        for i in range(q_entries):
            sd = SearchData(
                trace_id=rng.bytes(16),
                start_s=int(rng.integers(1, 5_000)),
                end_s=int(rng.integers(5_000, 10_000)),
                dur_ms=int(rng.integers(1, 30_000)),
            )
            sd.root_service = svcs[int(rng.integers(0, len(svcs)))]
            sd.kvs = {"service.name": {sd.root_service},
                      "env": {"prod" if i % 2 else "dev"}}
            if rng.random() < 0.25:
                sd.kvs["error"] = {"true"}
            out.append(sd)
        return out

    def ref_series(corpus, pred):
        series = {}
        for sd in corpus:
            if not pred(sd):
                continue
            s = series.setdefault(sd.root_service, {
                "calls": 0, "errors": 0,
                "hist": [0] * (len(MS_BUCKETS) + 1)})
            s["calls"] += 1
            s["errors"] += int("true" in sd.kvs.get("error", ()))
            s["hist"][_bisect.bisect_left(MS_BUCKETS, sd.dur_ms)] += 1
        return series

    preds = {
        "env=prod": lambda sd: "prod" in next(iter(sd.kvs["env"])),
        "env=dev": lambda sd: "dev" in next(iter(sd.kvs["env"])),
        "svc-03": lambda sd: "svc-03" == sd.root_service,
    }
    tag_of = {"env=prod": ("env", "prod"), "env=dev": ("env", "dev"),
              "svc-03": ("service.name", "svc-03")}

    with tempfile.TemporaryDirectory() as td:
        be = LocalBackend(td + "/blocks")
        db = TempoDB(be, td + "/wal", TempoDBConfig(
            auto_mesh=False, search_analytics_enabled=True))
        corpus = []
        for s in range(3):
            entries = sorted(mk_entries(s), key=lambda sd: sd.trace_id)
            corpus.extend(entries)
            db.write_block_direct(
                "bench",
                [(sd.trace_id, encode_search_data(sd), sd.start_s,
                  sd.end_s) for sd in entries],
                search_entries=entries)

        def run(name, agg):
            k, v = tag_of[name]
            req = tempopb.SearchRequest()
            req.limit = len(corpus)
            req.tags[k] = v
            if agg:
                attach_agg(req, "red")
            db.search("bench", req)        # warm
            t0 = time.perf_counter()
            for _ in range(q_rounds):
                resp = db.search("bench", req).response()
            return (time.perf_counter() - t0) / q_rounds, resp

        queries = {}
        agg_wall = plain_wall = 0.0
        for name, pred in preds.items():
            w_plain, _ = run(name, agg=False)
            w_agg, resp = run(name, agg=True)
            agg_wall += w_agg
            plain_wall += w_plain
            got = json.loads(resp.metrics.agg_json)
            want = ref_series(corpus, pred)
            assert got["series"] == want, (
                f"?agg=red diverged from the host reference on {name}")
            queries[name] = {
                "matches": sum(s["calls"] for s in want.values()),
                "plain_ms": round(w_plain * 1e3, 3),
                "agg_ms": round(w_agg * 1e3, 3),
            }

        query = {
            "entries": len(corpus),
            "rounds": q_rounds,
            "reference_identical": True,
            "agg_overhead_ratio": round(
                agg_wall / max(plain_wall, 1e-9), 3),
            "queries": queries,
        }

    return {"ingest": ingest, "query": query}


def phase_scale_10k():
    n_blocks = int(os.environ.get("BENCH_SCALE_BLOCKS", 10_000))
    if not n_blocks:
        return None
    return bench_scale(n_blocks,
                       int(os.environ.get("BENCH_SCALE_ENTRIES", 512)),
                       int(os.environ.get("BENCH_SCALE_ITERS", 7)))


def phase_scale_large_blocks():
    n_blocks = int(os.environ.get("BENCH_LARGE_BLOCKS", 600))
    if not n_blocks:
        return None
    return bench_scale_large(
        n_blocks,
        int(os.environ.get("BENCH_LARGE_ENTRIES", 65_536)),
        int(os.environ.get("BENCH_LARGE_ITERS", 3)))


PHASES = {
    "probe": phase_probe,
    "single": phase_single,
    "multiblock": phase_multiblock,
    "serving": phase_serving,
    "coalesced_serving": phase_coalesced_serving,
    "high_cardinality": phase_high_cardinality,
    "high_cardinality_full": phase_high_cardinality_full,
    "profile_overhead": phase_profile_overhead,
    "query_stats_overhead": phase_query_stats_overhead,
    "selftrace_overhead": phase_selftrace_overhead,
    "freshness": phase_freshness,
    "chaos": phase_chaos,
    "ownership": phase_ownership,
    "packing": phase_packing,
    "structural": phase_structural,
    "analytics": phase_analytics,
    "scale_10k": phase_scale_10k,
    "scale_large_blocks": phase_scale_large_blocks,
}

# Per-phase subprocess deadlines (seconds); env-overridable via
# BENCH_TIMEOUT_<NAME>. Sized ~3x the r4 self-run wall times so a healthy
# run never trips them, while a wedge loses only the phase it hit.
PHASE_TIMEOUTS = {
    "probe": 60.0,
    "single": 420.0,
    "multiblock": 300.0,
    "serving": 420.0,
    "coalesced_serving": 420.0,
    "high_cardinality": 300.0,
    "high_cardinality_full": 420.0,
    "profile_overhead": 300.0,
    "query_stats_overhead": 300.0,
    "selftrace_overhead": 300.0,
    "freshness": 560.0,  # baseline leg + hot-tier gate-on leg + tail
    "chaos": 420.0,
    "ownership": 540.0,
    "packing": 420.0,
    "structural": 600.0,
    "analytics": 420.0,
    "scale_10k": 900.0,
    "scale_large_blocks": 1200.0,
}


# env keys that change a phase's MEASUREMENT (platform + corpus sizes);
# harness plumbing (ckpt paths, deadlines, test hooks) is excluded. Used
# to fingerprint checkpoints so BENCH_RESUME never mixes results across
# platforms or corpus configs.
_FP_EXCLUDE = ("BENCH_CKPT", "BENCH_RESUME", "BENCH_WATCHDOG",
               "BENCH_TIMEOUT", "BENCH_PHASES", "BENCH_TEST")


def _fingerprint(env: dict) -> dict:
    knobs = {k: v for k, v in sorted(env.items())
             if k.startswith("BENCH_") and not k.startswith(_FP_EXCLUDE)}
    return {"jax_platforms": env.get("JAX_PLATFORMS", ""), "knobs": knobs}


def _phase_main(name: str) -> int:
    """Child entry: run one phase, print its result as the last stdout
    line, and checkpoint it to $BENCH_CKPT_FILE (atomic rename) so the
    number survives even if the parent dies before reading the pipe."""
    hang = os.environ.get("BENCH_TEST_HANG_PHASE")
    if hang == name:  # test hook: simulate a device that stopped answering
        # BENCH_TEST_HANG_TIMES=N hangs only the first N attempts (counted
        # across child processes via a sidecar file) so tests can model a
        # device that answers a later preflight attempt
        times = int(os.environ.get("BENCH_TEST_HANG_TIMES", 0))
        cnt_path = (os.environ.get("BENCH_CKPT_FILE") or name) + ".hangcount"
        try:
            with open(cnt_path) as f:
                n = int(f.read().strip() or 0) + 1
        except (OSError, ValueError):
            n = 1
        with open(cnt_path, "w") as f:
            f.write(str(n))
        if times <= 0 or n <= times:
            while True:
                time.sleep(3600)

    from tempo_tpu.utils.jaxenv import enable_compile_cache

    # every phase child shares one persistent XLA compile cache: later
    # phases replay shared kernel compiles from disk
    enable_compile_cache()
    result = PHASES[name]()
    if isinstance(result, dict) and "_profile" not in result:
        # per-phase dispatch-stage breakdown (observability/profile.py):
        # each phase child is its own process, so the process profiler's
        # aggregates ARE this phase's stage profile — the trajectory
        # files stop being opaque wall-clock totals
        try:
            from tempo_tpu.observability.profile import PROFILER

            snap = PROFILER.snapshot(recent=0)
            if snap["aggregates"]:
                result["_profile"] = {
                    "aggregates": snap["aggregates"],
                    "jit_cache": snap["jit_cache"],
                    "bytes": snap["bytes"],
                }
        except Exception:  # noqa: BLE001 — telemetry must not fail a phase
            pass
    if isinstance(result, dict) and "_breaker" not in result:
        # the device circuit breaker's verdict rides every phase result:
        # a phase whose dispatches tripped the breaker mid-run is a
        # wedge the HEADLINE must see (sourced from breaker state, not
        # ad-hoc probing). The chaos phase resets
        # its deliberate trips before returning, so this only fires on
        # a REAL wedge.
        try:
            from tempo_tpu.robustness import BREAKER

            snap = BREAKER.snapshot()
            if snap["transitions"] or snap["faults_in_window"]:
                result["_breaker"] = snap
        except Exception:  # noqa: BLE001 — telemetry must not fail a phase
            pass
    doc = json.dumps(result)
    ckpt = os.environ.get("BENCH_CKPT_FILE")
    if ckpt:
        tmp = ckpt + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"_fp": _fingerprint(dict(os.environ)),
                       "data": result}, f)
        os.replace(tmp, ckpt)
    print(doc, flush=True)
    return 0


# ---------------------------------------------------------------------------
# Orchestrator — stdlib only; NEVER imports jax: the chip belongs to one
# process at a time (the phase child), and a hung device op sits in C code,
# uninterruptibly — only a subprocess kill works.
# ---------------------------------------------------------------------------

_current_child: subprocess.Popen | None = None


def _kill_child(p: subprocess.Popen) -> None:
    try:
        os.killpg(p.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        p.kill()


def _run_child(name: str, timeout_s: float, ckpt_dir: str,
               timeout_reason: str = "device likely hung"):
    """Run one phase subprocess; on wedge/timeout SIGKILL its whole
    process group and fall back to its checkpoint file if one landed.
    Only a checkpoint written by THIS child counts — a stale file from
    a previous (resumed) run must not make a wedged device look healthy."""
    global _current_child
    path = os.path.join(ckpt_dir, f"{name}.json")
    env = dict(os.environ)
    env["BENCH_CKPT_FILE"] = path
    t_child_start = time.time()
    p = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", name],
        stdout=subprocess.PIPE, stderr=None, text=True,
        start_new_session=True, env=env, cwd=_HERE)
    _current_child = p

    def fresh_ckpt():
        try:
            if os.path.getmtime(path) >= t_child_start - 1.0:
                with open(path) as f:
                    obj = json.load(f)
                if isinstance(obj, dict) and "_fp" in obj:
                    return obj["data"]
                return obj
        except OSError:
            pass
        return None

    try:
        out, _ = p.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        _kill_child(p)
        p.wait()
        return fresh_ckpt() or {
            "error": f"phase '{name}' timed out after {timeout_s:.0f}s "
                     f"— {timeout_reason}; phase killed"}
    finally:
        _current_child = None
    if p.returncode != 0:
        return fresh_ckpt() or {
            "error": f"phase '{name}' exited rc={p.returncode}"}
    for line in reversed((out or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{") or line == "null":
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return fresh_ckpt() or {
        "error": f"phase '{name}' produced no parseable result"}


def _failed(r) -> bool:
    return isinstance(r, dict) and "error" in r


def _assemble(results: dict) -> dict:
    """Build the single final JSON doc from whatever phases finished;
    hung or failed phases carry {"error": ...} instead of numbers."""
    def _strip(r):
        """Phase result without its `_profile`/`_breaker` riders (those
        land once, under detail, not duplicated per config)."""
        if isinstance(r, dict) and ("_profile" in r or "_breaker" in r):
            return {k: v for k, v in r.items()
                    if k not in ("_profile", "_breaker")}
        return r

    # per-phase dispatch-stage profiles, collected before the strip
    prof_stages = {k: v["_profile"] for k, v in results.items()
                   if isinstance(v, dict) and "_profile" in v}
    # phases whose device circuit breaker was NOT closed at exit — a
    # mid-phase wedge the headline must surface, sourced from breaker
    # state rather than ad-hoc probing (the chaos phase's deliberate
    # trips reset before return, so anything here is real)
    breaker_wedged = {
        k: v["_breaker"] for k, v in results.items()
        if isinstance(v, dict)
        and v.get("_breaker", {}).get("state") not in (None, "closed")}
    results = {k: _strip(v) for k, v in results.items()}
    single = results.get("single")
    probe = results.get("probe") or {}
    ok = isinstance(single, dict) and not _failed(single)
    tpu_rate = single["tpu_traces_per_sec"] if ok else 0
    cpu_rate = single["cpu_traces_per_sec"] if ok else 0
    serving = results.get("serving")
    if isinstance(serving, dict) and not _failed(serving) \
            and "sync_ms" in probe:
        serving = dict(serving)
        serving["sync_floor_ms"] = probe["sync_ms"]
    doc = {
        "metric": "columnar_tag_scan_throughput",
        "value": tpu_rate,
        "unit": "traces/s",
        "vs_baseline": round(tpu_rate / cpu_rate, 3) if ok and cpu_rate else 0,
        "detail": {
            "platform": probe.get("platform", "unknown"),
            "device_kind": probe.get("device_kind", "unknown"),
            "device_count": probe.get("device_count", 0),
            "device": probe.get("device", "unknown"),
            "n_entries": (single or {}).get("n_entries"),
            "matches": (single or {}).get("matches"),
            "cpu_traces_per_sec": cpu_rate,
            "query": "service.name=svc-007 AND http.status_code=500 AND dur>=500ms",
            "configs": {
                "duration_only_traces_per_sec":
                    (single or {}).get("duration_only_traces_per_sec")
                    if ok else None,
                "multiblock": results.get("multiblock"),
                "serving_path": serving,
                "coalesced_serving": results.get("coalesced_serving"),
                "high_cardinality": results.get("high_cardinality"),
                "high_cardinality_full": results.get("high_cardinality_full"),
                "scale_10k": results.get("scale_10k"),
                "scale_large_blocks": results.get("scale_large_blocks"),
            },
        },
    }
    # the dictionary-probe trajectory (host prefilter vs device probe)
    # surfaces at the TOP level of detail so round-over-round consumers
    # track the optimization without digging through per-phase configs
    probe_ms = {}
    for ph in ("high_cardinality", "high_cardinality_full"):
        r = results.get(ph)
        if isinstance(r, dict) and not _failed(r):
            probe_ms[ph] = {
                "distinct_values": r.get("distinct_values"),
                "dict_prefilter_ms": r.get("dict_prefilter_ms"),
                "device_probe_ms": r.get("device_probe_ms"),
                "device_probe_stage_ms": r.get("device_probe_stage_ms"),
            }
    if probe_ms:
        doc["detail"]["dict_probe"] = probe_ms
    # offload-planner calibration table (predicted vs measured stage
    # times, decisions taken, mispredict rate) — the high-cardinality
    # phases run planner-on with identical-match asserts and ship the
    # verdicts here, spanning the measured crossover (1M and 10M values)
    planner_tbl = {}
    for ph in ("high_cardinality", "high_cardinality_full"):
        r = results.get(ph)
        if isinstance(r, dict) and not _failed(r) and r.get("planner"):
            planner_tbl[ph] = dict(r["planner"],
                                   distinct_values=r.get("distinct_values"))
    if planner_tbl:
        doc["detail"]["planner"] = planner_tbl
    # dispatch-profiler telemetry: the overhead contract measurement plus
    # every phase's per-(mode, stage) aggregates — the trajectory now
    # carries WHERE device time went, not just wall-clock totals
    prof: dict = {}
    ov = results.get("profile_overhead")
    if isinstance(ov, dict) and not _failed(ov):
        prof["overhead"] = ov
    elif isinstance(ov, dict):
        prof["overhead"] = {"error": ov.get("error")}
    if prof_stages:
        prof["stages"] = prof_stages
    if prof:
        doc["detail"]["profile"] = prof
    # per-query stats noop/overhead contract rides the trajectory like
    # the profiler's (byte_identical + within_2pct are the acceptance)
    qso = results.get("query_stats_overhead")
    if isinstance(qso, dict):
        doc["detail"]["query_stats"] = (
            qso if not _failed(qso) else {"error": qso.get("error")})
    # dogfood self-trace gate: noop byte-identity + <2% request
    # overhead, tracked like the profiler/query-stats contracts
    sto = results.get("selftrace_overhead")
    if isinstance(sto, dict):
        doc["detail"]["selftrace"] = (
            sto if not _failed(sto) else {"error": sto.get("error")})
    # search-freshness SLO: push->searchable p50/p99 under soak write
    # load + the write-path telemetry contracts (gauge-vs-canary
    # agreement, noop byte-identity, <2% ack overhead) — ROADMAP item
    # 4's acceptance instrumentation, tracked round over round
    fr = results.get("freshness")
    if isinstance(fr, dict):
        doc["detail"]["freshness"] = (
            fr if not _failed(fr) else {"error": fr.get("error")})
    if not ok:
        err = (single or {}).get(
            "error", "headline phase 'single' did not run")
        if err.startswith("skipped: not selected"):
            # an explicit BENCH_PHASES subset without the headline is a
            # deliberate partial run, not a device failure
            doc["partial"] = err
        else:
            doc["error"] = err
    # robustness contract: the chaos phase's noop/fallback/recovery
    # asserts, tracked round over round like the other noop contracts
    ch = results.get("chaos")
    if isinstance(ch, dict):
        doc["detail"]["chaos"] = (
            ch if not _failed(ch) else {"error": ch.get("error")})
    # packed-residency contract: physical-bytes saved, byte-identity,
    # and the fixed-budget residency/hit-ratio split (ISSUE 13) —
    # tracked round over round like the other noop contracts
    pk = results.get("packing")
    if isinstance(pk, dict):
        doc["detail"]["packing"] = (
            pk if not _failed(pk) else {"error": pk.get("error")})
    if breaker_wedged:
        # breaker-sourced wedge signal: some phase ended with its
        # breaker open/half-open — a real mid-run device failure
        doc["device_wedged"] = True
        doc.setdefault(
            "wedge_reason",
            "circuit breaker open at phase exit: "
            + ", ".join(f"{k}={v['state']}"
                        for k, v in sorted(breaker_wedged.items())))
        doc["detail"]["breaker"] = breaker_wedged
    if results.get("terminated"):
        doc["terminated"] = results["terminated"]
    if probe.get("platform") == "cpu":
        # a dry run of the harness, not a measurement: the headline is
        # the device's and stays empty; what the phases printed sits in
        # detail.configs under a doc that names platform cpu
        doc["value"] = 0
        doc["vs_baseline"] = 0
        doc.setdefault("error", "no accelerator (platform cpu): harness "
                                "dry run, nothing here is a device metric")
    return doc


def orchestrate() -> int:
    # default budget covers a healthy full run plus ONE hung phase burning
    # its largest deadline (1200 s); with several hangs the remaining
    # phases are skipped with explicit errors rather than lost
    budget = float(os.environ.get("BENCH_WATCHDOG_S", 3600))
    t_start = time.perf_counter()

    def time_left():
        if budget <= 0:
            return float("inf")
        return budget - (time.perf_counter() - t_start)

    ckpt_dir = os.environ.get(
        "BENCH_CKPT_DIR", os.path.join(_HERE, "benchmarks", ".bench_ckpt"))
    resume = os.environ.get("BENCH_RESUME", "0") not in ("0", "")
    os.makedirs(ckpt_dir, exist_ok=True)
    if not resume:
        for f in os.listdir(ckpt_dir):
            p = os.path.join(ckpt_dir, f)
            if os.path.isfile(p):
                os.unlink(p)

    results: dict = {}

    def emit_and_exit(rc: int) -> int:
        doc = _assemble(results)
        with open(os.path.join(ckpt_dir, "final.json"), "w") as f:
            json.dump(doc, f)
        print(json.dumps(doc), flush=True)
        return rc

    # a driver-side SIGTERM must still yield the completed phases' numbers
    # — and must not orphan the in-flight phase child on the device
    def on_term(signum, frame):
        if _current_child is not None:
            _kill_child(_current_child)
        results.setdefault("terminated", f"signal {signum}")
        doc = _assemble(results)
        try:
            with open(os.path.join(ckpt_dir, "final.json"), "w") as f:
                json.dump(doc, f)
        except OSError:
            pass
        print(json.dumps(doc), flush=True)
        os._exit(3)

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)  # Ctrl-C must not orphan a child

    # validate phase selection BEFORE spending minutes on preflight
    phase_order = [p for p in PHASES if p != "probe"]
    want = os.environ.get("BENCH_PHASES")
    if want:
        sel = [w.strip() for w in want.split(",") if w.strip()]
        unknown = sorted(set(sel) - set(PHASES))
        if unknown:  # fail fast — a typo must not silently drop a phase
            print(f"bench: unknown BENCH_PHASES {unknown}; "
                  f"valid: {sorted(PHASES)}", file=sys.stderr, flush=True)
            results["single"] = {"error":
                                 f"unknown BENCH_PHASES {unknown}"}
            return emit_and_exit(2)
        phase_order = [p for p in phase_order if p in sel]

    # --- preflight: the device answers, or nothing is measured ---
    # BENCH_PREFLIGHT_ATTEMPTS (default 1): one hang is already a strong
    # signal; operators chasing a flaky (not dead) device can raise it.
    # The per-attempt deadline is BENCH_TIMEOUT_PROBE (seconds).
    probe_deadline = float(os.environ.get(
        "BENCH_TIMEOUT_PROBE", PHASE_TIMEOUTS["probe"]))
    n_attempts = max(1, int(os.environ.get("BENCH_PREFLIGHT_ATTEMPTS", 1)))
    attempts = []
    for i in range(n_attempts):
        if time_left() < 10:
            break
        r = _run_child("probe", min(probe_deadline, time_left()),
                       ckpt_dir)
        if not _failed(r):
            results["probe"] = r
            break
        attempts.append(r["error"])
        print(f"bench: preflight attempt {i + 1}/{n_attempts} failed: "
              f"{r['error']}", file=sys.stderr, flush=True)
    if "probe" not in results:
        results["probe"] = {"error": "; ".join(attempts) or
                            "probe never ran (budget exhausted)"}
        results["single"] = {"error": "skipped: no healthy device "
                                      "(preflight probe failed "
                                      f"{len(attempts)}x)"}
        return emit_and_exit(3)

    for name in phase_order:
        ck = os.path.join(ckpt_dir, f"{name}.json")
        if resume and os.path.exists(ck):
            # only reuse a checkpoint whose platform + corpus knobs match
            # THIS run — a prior CPU dry run or differently-sized run
            # must re-measure, not masquerade as current numbers
            try:
                with open(ck) as f:
                    obj = json.load(f)
            except (OSError, json.JSONDecodeError):
                obj = None
            if (isinstance(obj, dict) and
                    obj.get("_fp") == _fingerprint(dict(os.environ))):
                results[name] = obj["data"]
                continue
            print(f"bench: resume checkpoint for {name} is from a "
                  "different platform/config — re-running",
                  file=sys.stderr, flush=True)
        deadline = float(os.environ.get(
            f"BENCH_TIMEOUT_{name.upper()}", PHASE_TIMEOUTS[name]))
        remaining = time_left() - 20  # reserve for assembly/emission
        if remaining < 30:
            results[name] = {"error": "skipped: global bench budget "
                                      f"({budget:.0f}s) exhausted"}
            continue
        reason = ("global bench budget truncation — phase may be healthy"
                  if remaining < deadline
                  else "phase deadline — device likely hung")
        t0 = time.perf_counter()
        results[name] = _run_child(name, min(deadline, remaining),
                                   ckpt_dir, timeout_reason=reason)
        status = "FAILED" if _failed(results[name]) else "ok"
        print(f"bench: phase {name} {status} "
              f"({time.perf_counter() - t0:.1f}s)",
              file=sys.stderr, flush=True)
        with open(os.path.join(ckpt_dir, "partial.json"), "w") as f:
            json.dump(_assemble(results), f)

    if "single" not in phase_order and "single" not in results:
        # deliberate partial selection: the headline is absent by choice
        results["single"] = {"error": "skipped: not selected "
                                      "(BENCH_PHASES)"}
    # exit 0 only for a device run in which every selected phase
    # succeeded: a failed phase is 3 whatever else finished, and a run
    # on platform cpu is 4 (harness dry run, no device metric)
    if any(_failed(results.get(p, {"error": "missing"}))
           for p in phase_order):
        return emit_and_exit(3)
    return emit_and_exit(4 if results["probe"].get("platform") == "cpu"
                         else 0)


def main() -> int:
    if len(sys.argv) >= 2 and sys.argv[1] == "--phase":
        if len(sys.argv) < 3 or sys.argv[2] not in PHASES:
            got = sys.argv[2] if len(sys.argv) >= 3 else "(missing)"
            print(json.dumps({"error": f"unknown phase {got!r}; "
                              f"valid: {sorted(PHASES)}"}), flush=True)
            return 2
        return _phase_main(sys.argv[2])
    return orchestrate()


if __name__ == "__main__":
    sys.exit(main())
