"""One traced chipbench run, and what its spans say beyond the result line.

    python3 scripts/trace_report.py --workload share16.triage --seed 7 \
        --seconds 40 [--trace 0] [--scale tiny]

Runs the cell through `chipbench.run.run` (the benchmark's own code, no
copy of it) and, from the run's spans, prints one `TRACE_REPORT {json}`
line and writes it to
`chiprun_out/trace_report.<cell>.<seed>.t<trace>.json`:

  end_to_end   the cell's end-to-end readers on THIS run, so a traced
               and an untraced run of one seed give the cost of tracing
  topk         inside the window: launches by top-k path
               (`tempo_search_topk_dispatches_total{path}`) beside the
               device dispatches, and the `dispatch.execute` spans by
               their `topk` attribute
  mesh         what a mesh adds, and what a skewed split would show:
               launches in the window by mode and `shards`
               (`tempo_search_scan_dispatches_total`) and per second,
               whether their parameters were resident on the mesh
               (`tempo_search_mesh_param_placements_total`), groups staged and
               evictions in the window, the share of the window's
               searches that inspected the whole tenant, per launch the
               profiler's stage seconds (`lock_wait` among them), bytes
               in use and peak per device, and from the profiler's
               trace each device's busy seconds and the collective ops
               by name (`collective_share.mesh`'s own rule)
  staging      what the staged-batch cache did: its events in the window
               and since the start by result (`hit`, `miss`, `evict`,
               `host_hit`, `host_miss`, `host_evict`), bytes evicted,
               the HBM gauge, its high water and the host tier's, the
               `h2d` stage, `batcher.stage` by `cache` and the
               `batcher.place` spans, completed searches by template;
               the groups searches took by the rule that chose each
               (`tempo_search_group_picks_total{pick}`: `resident`,
               `joined` another search's put, `staged` it), their
               shares, and puts per completed search
  probe        what the dictionary probe and the membership test did:
               probes in the window and since the start by path
               (`tempo_search_dict_probes_total{path}`: `device`,
               `host`, `cached`), the `dict_probe` dispatches' `execute`
               stage since the start, launch members by membership
               (`tempo_search_scan_membership_total{path}`: `range`,
               `mask`) and by what their range compares test
               (`tempo_search_scan_range_compare_total{by}`: `slot`,
               `entry`), the `dispatch.execute` spans by `membership`
               and by `compare`,
               the `dict_probe.probe` spans by `path` and `membership`
               with their longest `runs_max`, the HBM that dictionaries
               and hit masks hold, and from the profiler's trace the
               scan programs apart: `batch_scan_kernel` (compares only)
               and `mask_scan_kernel` (takes `val_hits`), calls and ms a
               launch each, and the probe program
  enqueue_split  what the kernel call of a solo mesh launch, which is what
               the collective lock is held for, costs the host, by where
               the query's parameters are (jit places arguments in C++,
               where no Python profile looks, so the arms are run): after
               the window, the process quiet, one thread, 200 enqueues in
               a row over one resident group, with the parameters on
               device 0 as PR 26 placed them, with them resident on the
               mesh, and the `jax.device_put` that places them alone.
               In the window the same call is the `execute` stage of
               `mesh.stages`, one thread among many, GIL waits included
  host         what the host's cores did (`chipbench/layers/hostcpu.py`
               holds the arithmetic): `host_cores_busy`, the delta of
               `process_cpu_seconds_total` (and its user and system
               parts) over the window, which `--trace 0` reads too; by
               span name over the window `n` and, per search, ms of
               `wall_self`, `cpu_self` and `off_self` (a span's wall
               and `thread.cpu_ns` less those of the spans of ITS
               THREAD directly inside it; off = wall - cpu of the
               name's SUMS: the thread off a core), with `named_wait`
               on the spans whose code blocks by design; the four span
               metrics; `spanned_cores`, the CPU the spans' threads
               burned together over the time from the first search's
               start to the last one's end (1.0 is one interpreter
               lock's worth); the put's
               `cpu_over_wall` from `batcher.place` (a put that burns
               its wall on a core holds the host, one that sleeps
               does not); and the launch's stages apart by the launch's
               `mode` (`batched` solo, `coalesced` fused, `mesh` either
               on a mesh): per `dispatch.<stage>` span and mode `n`, ms
               of `wall_self` and `cpu_self` a span and `cpu_self` a
               search (`dispatch_by_mode`), and the host arrays a launch
               put on the device for its query tables
               (`param_puts_per_launch`:
               `tempo_search_launch_param_puts_total{mode}` over the
               `build` stages counted, which `--trace 0` reads too; a
               fused launch puts 1, a solo launch whose predicate is
               resident 0), and the host arrays its drain fetched back
               (`out_fetches_per_launch`:
               `tempo_search_launch_out_fetches_total{mode}` over the
               same launches: 1, the one packed output; under 1 by the
               launches no drain waited for; 0 on a tree without the
               counter, which fetched four)
  coverage     per search: how much of `http.request` (accept -> last
               byte written) its child spans cover, the wait before the
               handler (its `accept_wait_ms`) counted with them
  slowest      for the slowest 5 % of searches (by `http.request`): self
               time (a span's duration less what its children cover)
               summed by span name, per search, without the overlays
               (`device.scan`, `coalescer.wait`); a search's
               sub-requests run side by side and an inline
               `coalescer.launch` lies inside its sibling
               `batcher.dispatch`, so the sum can pass the wall time; and
               the share of it in spans that have children (time no
               span explains)

Only what the spans alone give: nothing here is matched against the
profiler's trace (that needs the harness to keep its `zero_wall_ns`,
PERF.md section 7a). A traced run's spans also go to
`chiprun_out/spans.<cell>.<seed>.json.gz`. `--trace 0` runs untraced and
reports `end_to_end` alone. The last line of stdout is the benchmark's
own result line, as `chipbench.run` prints it.
"""

from __future__ import annotations

import argparse
import collections
import gzip
import json
import os
import resource
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import run as bench_run  # noqa: E402
from chipbench.layers import spans as sp  # noqa: E402
from chipbench.lib import delta, median, percentile  # noqa: E402
from chipbench.xplane import merge  # noqa: E402

# spans that overlay the host's own: the device's timeline, and a
# member's wait for its launch, which lies over the `batcher.dispatch`
# or `batcher.drain` the member's thread was in meanwhile
OVERLAYS = {sp.DEVICE, "coalescer.wait"}


def enqueue_split(server, calls: int = 200):
    """What a solo mesh launch's kernel call costs the host, by where
    its six query parameters are: after the window, the process quiet,
    one thread, `calls` enqueues in a row of the kernel the window ran,
    over one resident group and one of its memoised predicates, the
    collective lock held throughout. Three arms, ms a call:

      as_parent  the parameters uncommitted on device 0
                 (`jnp.asarray`, `jnp.uint32`: PR 26's placement), so
                 jit moves them to every device inside the call
      put        `jax.device_put` of the six host values to the mesh,
                 replicated, and nothing else: what placing them costs
                 when it is done ahead of the call
      resident   the parameters put on the mesh once, before the loop:
                 the call is the enqueue alone

    Each arm's loop is timed to the last enqueue's return (`enqueue`)
    and to its outputs ready (`drained`). None off a mesh, or when the
    group's memo holds no plain predicate."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from tempo_tpu.parallel import mesh as mesh_mod
    from tempo_tpu.search import multiblock
    from tempo_tpu.search.engine import resolve_top_k

    batcher = server.app.reader_db.batcher
    eng = batcher.engine
    if eng.mesh is None:
        return None
    cache = batcher.cache
    held = [c for c in map(cache.resident, cache.snapshot()["entries"])
            if c is not None]
    with cache.group_lock:       # the memos change under it
        found = next(((c.batch, pre) for c in held
                      for pre in c.query_cache.values()
                      if not pre["all_skip"] and pre.get("val_hits") is None
                      and pre.get("structural") is None), None)
    if found is None:
        return None
    batch, pre = found
    d = batch.device
    host = (np.asarray(pre["term_keys"]), np.asarray(pre["val_ranges"]),
            *(np.uint32(min(int(pre[k]), 0xFFFFFFFF))
              for k in ("dur_lo", "dur_hi", "win_start", "win_end")))
    spec = NamedSharding(eng.mesh, PartitionSpec())

    def launch(params):
        tk, vr, *bounds = params
        return multiblock.batch_scan_kernel(
            d["kv_key"], d["kv_val"], d["entry_start"],
            d["entry_end"], d["entry_dur"], d["entry_valid"],
            d["page_block"], tk, vr, None, *bounds, None, None,
            d.get("entry_dur_res"), None, None, None, mesh=eng.mesh,
            n_terms=pre["n_terms"],
            top_k=resolve_top_k(eng.top_k, 20), widths=batch.widths,
            plan=None, span_sharded=False,
            shard_tail=eng._shard_tail(batch, d), agg=None)

    on_zero = (jnp.asarray(host[0]), jnp.asarray(host[1]),
               *(jnp.uint32(int(v)) for v in host[2:]))
    resident = jax.device_put(host, spec)
    arms = {"as_parent": lambda: launch(on_zero),
            "put": lambda: jax.device_put(host, spec),
            "resident": lambda: launch(resident)}
    out = {"calls": calls, "pages_per_shard": eng.pages_per_shard(batch)}
    with mesh_mod.dispatch_lock:
        for name, arm in arms.items():
            jax.block_until_ready(arm())      # compile, if new here
            t = time.perf_counter()
            for _ in range(calls):
                last = arm()
            enqueue = time.perf_counter() - t
            jax.block_until_ready(last)
            out[name] = {
                "enqueue_ms": enqueue / calls * 1e3,
                "drained_ms": (time.perf_counter() - t) / calls * 1e3}
    return out


def total(iv: list) -> int:
    return sum(b - a for a, b in iv)


def inside(a: list, b: list) -> list:
    """The part of the union `a` that lies inside the union `b` (both
    sorted and disjoint, as `merge` leaves them)."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            out.append([max(lo, b[k][0]), min(hi, b[k][1])])
            k += 1
    return out


def self_ns(spans: list) -> dict:
    """Self time of one trace's spans, summed by name: name -> ns."""
    kids: dict = {}
    for s in spans:
        if s["parent_id"] and s["name"] not in OVERLAYS:
            kids.setdefault(s["parent_id"], []).append(
                [s["start_ns"], s["end_ns"]])
    out: dict = {}
    for s in spans:
        if s["name"] in OVERLAYS:
            continue
        own = [[s["start_ns"], s["end_ns"]]]
        covered = total(inside(
            merge(kids.get(s["span_id"], [])), own))
        out[s["name"]] = out.get(s["name"], 0) + total(own) - covered
    return out


def mesh_facts(view: dict) -> dict:
    """The `mesh` block: counters, answers and trace of one run."""
    import jax

    from chipbench.lib import metric_sum
    from chipbench.ops.search import work

    is_collective = bench_run.load_reader(
        "layers", "collective_share.mesh").is_collective
    name = "tempo_search_scan_dispatches_total"
    launches = {}
    for lab, v in view["counters"]["after"].get(name, {}).items():
        d = v - view["counters"]["before"].get(name, {}).get(lab, 0.0)
        if d:
            launches[lab] = d
    cache = "tempo_search_batch_cache_events_total"
    stage = "tempo_search_dispatch_stage_seconds"
    stages = {}
    for st in ("build", "execute", "compile", "lock_wait", "d2h"):
        n = delta(view, stage + "_count", stage=st)
        if n:
            stages[st] = {"launches": n, "ms_per_launch": delta(
                view, stage + "_sum", stage=st) / n * 1e3}
    done = [r for r in view["records"] if r["status"] == 200]
    whole = sum(work(None, r).get("inspected_entries")
                == view["manifest"]["entries"] for r in done)
    out = {
        "launches_in_window": launches,
        "launches_per_s": sum(launches.values()) / view["window_wall_s"],
        # absent on a tree that has no such counter: both read 0
        "param_placements": {r: delta(
            view, "tempo_search_mesh_param_placements_total", result=r)
            for r in ("placed", "reused")},
        "groups_staged": metric_sum(view["counters"]["after"], cache,
                                    result="miss"),
        "evictions_in_window": delta(view, cache, result="evict"),
        "whole_tenant_share": whole / len(done) if done else None,
        "stages": stages,
        "devices": [dict(id=d.id, **{
            k: int((d.memory_stats() or {}).get(k, 0))
            for k in ("bytes_in_use", "peak_bytes_in_use")})
            for d in jax.devices()]}
    trace = view.get("trace")
    if trace:
        out["busy_s"] = [d["busy_ns"] / 1e9 for d in trace["devices"]]
        out["collective_ops_s"] = {k: v / 1e9 for k, v in trace["ops_ns"]
                                   if is_collective(k)}
        out["ops_s"] = [[k, v / 1e9] for k, v in trace["ops_ns"][:25]]
        out["programs"] = {k: [trace["program_calls"][k], v / 1e9]
                           for k, v in trace["programs_ns"].items()}
    return out


def staging_facts(view: dict) -> dict:
    """The `staging` block: what the staged-batch cache did."""
    from chipbench.lib import metric_sum

    cache = "tempo_search_batch_cache_events_total"
    stage = "tempo_search_dispatch_stage_seconds"
    after = view["counters"]["after"]
    kinds = ("hit", "miss", "evict", "host_hit", "host_miss", "host_evict")
    by_cache: dict = {}
    for s in view["spans"]:
        if s["name"] == "batcher.stage":
            by_cache.setdefault(s["attributes"].get("cache"), []).append(
                (s["end_ns"] - s["start_ns"]) / 1e6)
    places = [s for s in view["spans"] if s["name"] == "batcher.place"]
    # absent on a tree that has no such counter: the three read 0
    picks = {k: delta(view, "tempo_search_group_picks_total", pick=k)
             for k in ("resident", "joined", "staged")}
    visits = sum(picks.values())
    done = sum(r["status"] == 200 for r in view["records"])
    return {
        "events_in_window": {k: delta(view, cache, result=k) for k in kinds},
        "picks_in_window": picks,
        "pick_shares": ({k: v / visits for k, v in picks.items()}
                        if visits else None),
        "puts_per_search": (delta(view, cache, result="miss") / done
                            if done else None),
        "events_since_start": {k: metric_sum(after, cache, result=k)
                               for k in kinds},
        # absent on a tree that has no such counter or gauge: they read 0
        "evicted_bytes_in_window": delta(
            view, "tempo_search_hbm_evicted_bytes_total"),
        "hbm_cache_bytes": metric_sum(after, "tempo_search_hbm_cache_bytes"),
        "hbm_cache_peak_bytes": metric_sum(
            after, "tempo_search_hbm_cache_peak_bytes"),
        "host_cache_bytes": metric_sum(after,
                                       "tempo_search_host_cache_bytes"),
        "h2d_stage_in_window": {
            "puts": delta(view, stage + "_count", stage="h2d"),
            "seconds": delta(view, stage + "_sum", stage="h2d"),
            "bytes": delta(view, "tempo_search_h2d_bytes_total")},
        "stage_ms_by_cache": {str(k): {
            "n": len(v), "mean": sum(v) / len(v), "max": max(v)}
            for k, v in by_cache.items()},
        "place_spans": {
            "n": len(places),
            "bytes": sum(s["attributes"].get("bytes", 0) for s in places),
            "seconds": sum(s["end_ns"] - s["start_ns"]
                           for s in places) / 1e9},
        # the process's high water of resident memory (Linux: KiB)
        "max_rss_bytes": resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024,
        "searches_by_op": dict(collections.Counter(
            view["requests"][r["i"]]["name"] for r in view["records"]
            if r["status"] == 200)),
    }


def probe_facts(view: dict) -> dict:
    """The `probe` block: dictionary probes and the membership test.
    On a tree without these counters and spans everything reads 0."""
    from chipbench.lib import metric_sum

    after = view["counters"]["after"]
    probes = "tempo_search_dict_probes_total"
    members = "tempo_search_scan_membership_total"
    spans = [s["attributes"] for s in view["spans"]
             if s["name"] == "dict_probe.probe"]
    out = {
        "probes_in_window": {k: delta(view, probes, path=k)
                             for k in ("device", "host", "cached")},
        "probes_since_start": {k: metric_sum(after, probes, path=k)
                               for k in ("device", "host", "cached")},
        "device_probe_ms_since_start": bench_run.load_reader(
            "layers", "probe_ms.highcard").compute(view),
        "launch_members_in_window": {k: delta(view, members, path=k)
                                     for k in ("range", "mask")},
        "launch_members_by_compare_in_window": {
            k: delta(view, "tempo_search_scan_range_compare_total", by=k)
            for k in ("slot", "entry")},
        **{f"execute_spans_by_{key}": dict(collections.Counter(
            s["attributes"].get(key, "absent")
            for s in view["spans"] if s["name"] == "dispatch.execute"))
           for key in ("membership", "compare")},
        "probe_spans": {
            "by_path": dict(collections.Counter(
                a.get("path") for a in spans)),
            "by_membership": dict(collections.Counter(
                a.get("membership") for a in spans)),
            "runs_max": max((a.get("runs_max", 0) for a in spans),
                            default=None)},
        "hbm": {"dict_bytes": metric_sum(
            after, "tempo_search_probe_dict_bytes"),
                "mask_bytes": metric_sum(
            after, "tempo_search_probe_mask_bytes"),
                "mask_peak_bytes": metric_sum(
            after, "tempo_search_probe_mask_peak_bytes"),
                "cache_bytes": metric_sum(
            after, "tempo_search_hbm_cache_bytes")}}
    trace = view.get("trace")
    if trace:
        for key, part in (("range_program", "batch_scan_kernel"),
                          ("mask_program", "mask_scan_kernel"),
                          ("probe_program", "probe_kernel")):
            calls = sum(v for k, v in trace["program_calls"].items()
                        if part in k)
            ns = sum(v for k, v in trace["programs_ns"].items() if part in k)
            out[key] = {"calls": calls,
                        "ms_per_launch": ns / calls / 1e6 if calls else None}
    return out


def host_facts(view: dict) -> dict:
    """The `host` block. On a tree without the counter or the CPU
    stamps the numbers are None and `by_span` is empty."""
    from chipbench.layers import hostcpu

    counted = hostcpu.PROCESS_CPU in view["counters"]["after"]
    rows = hostcpu.self_times(view["spans"])
    traces = sp.searches(view["spans"])
    n = len(traces) or None
    by_span: dict = {}
    by_mode: dict = {}
    for s, wall, cpu, away in rows:
        sums = [by_span.setdefault(s["name"], [0, 0, 0, 0])]
        if s["name"].startswith("dispatch."):
            sums.append(by_mode.setdefault(s["name"], {}).setdefault(
                s["attributes"].get("mode", "absent"), [0, 0, 0, 0]))
        for r in sums:
            r[0] += 1
            r[1] += wall
            r[2] += cpu
            # off a core in its own code, by name (never span by span:
            # where CPU is accounted by the tick only sums mean anything)
            r[3] += wall - cpu - away
    places = [s for s in hostcpu.stamped(view["spans"])
              if s["name"] == "batcher.place"]
    place_wall = sum(s["end_ns"] - s["start_ns"] for s in places)
    place_cpu = sum(s["attributes"][hostcpu.CPU] for s in places)
    # from the first search's start to the last one's end: the load,
    # without what the benchmark does after it (in a traced run the
    # read of the profiler's trace, which `window_wall_s` holds) and
    # without a poll that comes later still
    edges = [(s["start_ns"], s["end_ns"]) for ss in traces.values()
             for s in ss]
    extent = (max(b for _a, b in edges) - min(a for a, _b in edges)
              if edges else 0)
    spanned = sum(cpu for _s, _w, cpu, _a in rows)
    stage = "tempo_search_dispatch_stage_seconds_count"
    puts, fetches = {}, {}
    for mode in ("batched", "coalesced", "mesh"):
        launches = delta(view, stage, mode=mode, stage="build")
        if launches:
            # absent on a tree that has no such counter: reads 0
            puts[mode] = {
                "launches": launches,
                "puts_per_launch": delta(
                    view, "tempo_search_launch_param_puts_total",
                    mode=mode) / launches}
            fetches[mode] = {
                "launches": launches,
                "fetches_per_launch": delta(
                    view, "tempo_search_launch_out_fetches_total",
                    mode=mode) / launches}
    return {
        "host_cores_busy": hostcpu.cores_busy(view),
        "process_cpu_s": {
            part or "total": delta(view, "process_cpu_%sseconds_total"
                                   % (part and part + "_"))
            for part in ("", "user", "system")} if counted else None,
        "window_wall_s": view["window_wall_s"],
        "search_cpu_ms": hostcpu.search_cpu_ms(view),
        "launch_cpu_ms": hostcpu.launch_cpu_ms(view),
        "unnamed_offcore_share": hostcpu.unnamed_offcore_share(view),
        "spanned_cpu_share": hostcpu.spanned_cpu_share(view),
        # cores the spans' threads kept busy together: 1.0 is one
        # interpreter lock's worth
        "spanned_cpu_s": spanned / 1e9, "span_extent_s": extent / 1e9,
        "spanned_cores": spanned / extent if extent else None,
        "threads": len({s["attributes"][hostcpu.TID] for s, *_ in rows}),
        "searches": n,
        "by_span": {name: {
            "n": r[0], "named_wait": name in hostcpu.NAMED_WAITS,
            "wall_self_ms": r[1] / n / 1e6, "cpu_self_ms": r[2] / n / 1e6,
            "off_self_ms": max(0, r[3]) / n / 1e6}
            for name, r in sorted(by_span.items(),
                                  key=lambda kv: -kv[1][1])} if n else {},
        "dispatch_by_mode": {name: {mode: {
            "n": r[0], "wall_self_ms": r[1] / r[0] / 1e6,
            "cpu_self_ms": r[2] / r[0] / 1e6,
            "cpu_self_ms_per_search": r[2] / n / 1e6 if n else None}
            for mode, r in sorted(modes.items())}
            for name, modes in sorted(by_mode.items())},
        "param_puts_per_launch": puts,
        "out_fetches_per_launch": fetches,
        "place": {"n": len(places), "wall_s": place_wall / 1e9,
                  "cpu_s": place_cpu / 1e9,
                  "cpu_over_wall": (place_cpu / place_wall
                                    if place_wall else None)},
    }


def report(view: dict, e2e_names: list) -> dict:
    out: dict = {"workload": view["workload"], "end_to_end": {}}
    for name in e2e_names:
        v = bench_run.load_reader("metrics", name).compute(view)
        if v is not None:
            out["end_to_end"][name] = float(v)
    spans = view["spans"]
    out["topk"] = {
        "rows": delta(view, "tempo_search_topk_dispatches_total",
                      path="rows"),
        "direct": delta(view, "tempo_search_topk_dispatches_total",
                        path="direct"),
        "device_dispatches": sum(
            delta(view, "tempo_search_scan_dispatches_total", mode=m)
            for m in bench_run.DEVICE_MODES),
        "execute_spans": dict(collections.Counter(
            s["attributes"].get("topk", "absent") for s in spans
            if s["name"] == "dispatch.execute"))}
    out["mesh"] = mesh_facts(view)
    out["staging"] = staging_facts(view)
    out["probe"] = probe_facts(view)
    out["host"] = host_facts(view)
    traces = sp.searches(spans)
    if not traces:
        return out
    parents = {s["parent_id"] for s in spans if s["parent_id"]}
    rows = []
    for tid, ss in traces.items():
        roots = sp.named(ss, sp.REQUEST)
        if len(roots) != 1:
            continue
        root = roots[0]
        kids = merge([[c["start_ns"], c["end_ns"]] for c in ss
                         if c["parent_id"] == root["span_id"]])
        dur = root["end_ns"] - root["start_ns"]
        waited = root["attributes"].get("accept_wait_ms", 0.0) * 1e6
        rows.append({
            "ns": dur, "spans": len(ss),
            "covered": (waited + total(inside(
                kids, [[root["start_ns"] + waited, root["end_ns"]]]))) / dur,
            "by_span": self_ns(ss),
            # names of this trace's spans that have children: time that
            # falls to them is time no span explains
            "has_children": {s["name"] for s in ss
                             if s["span_id"] in parents}})
    out["searches"] = len(rows)
    out["spans_per_search_median"] = median([r["spans"] for r in rows])
    cov = [r["covered"] for r in rows]
    out["coverage"] = {"median": median(cov), "min": min(cov),
                       "p05": percentile([-c for c in cov], 95) * -1}
    rows.sort(key=lambda r: -r["ns"])
    for label, part in (("slowest", rows[:max(1, len(rows) // 20)]),
                        ("all", rows)):
        by_name: dict = {}
        unexplained = 0
        for r in part:
            for name, ns in r["by_span"].items():
                by_name[name] = by_name.get(name, 0) + ns
                if name in r["has_children"]:
                    unexplained += ns
        self_sum = sum(by_name.values())
        out[label] = {
            "searches": len(part),
            "mean_ms": sum(r["ns"] for r in part) / len(part) / 1e6,
            "self_over_wall": self_sum / sum(r["ns"] for r in part),
            "min_ms": min(r["ns"] for r in part) / 1e6,
            "ms_per_search_by_span": {
                k: v / len(part) / 1e6 for k, v in sorted(
                    by_name.items(), key=lambda kv: -kv[1])},
            "share_in_spans_with_children": unexplained / self_sum,
        }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--scale", choices=("full", "tiny"), default="full")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = [m["name"] for m in bench["end_to_end"]
           if args.workload in m.get("workloads", [args.workload])]
    got: dict = {}

    def hook(stage, state):
        if stage == "done":
            got["report"] = dict(
                report(state["run_view"], e2e),
                enqueue_split=enqueue_split(state["server"]))
            got["spans"] = state["run_view"]["spans"]

    result, code = bench_run.run(args, hook=hook)
    if "report" in got:
        rep = dict(got["report"], seed=args.seed, trace=args.trace,
                   seconds=args.seconds)
        line = json.dumps(rep)
        print("TRACE_REPORT " + line, flush=True)
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        stem = os.path.join(ROOT, "chiprun_out",
                            f"%s.{args.workload}.{args.seed}")
        with open(stem % "trace_report" + f".t{args.trace}.json", "w") as f:
            f.write(line + "\n")
        if got["spans"]:
            with gzip.open(stem % "spans" + ".json.gz", "wt") as f:
                json.dump(got["spans"], f)
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
