"""One run of one cell: `python3 -m chipbench.run --workload <name>
--seed <n> --seconds <s> --trace <0|1>`, from the root of a checkout.

One process is the benchmark and the server (chipbench/server.py); the
load comes from a child that never imports JAX (chipbench/client.py).
Everything that belongs to one cell, configuration, traffic mix, op or
metric is a file found by the name BENCHMARK.json gives it; this file
names none of them. The last line of stdout is the result object;
every earlier line is a fact about this run.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import urllib.request  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 5.0
EXIT_NO_CHIP = 2
EXIT_REHEARSAL = 3

# counters that must not move at all while the process lives: an answer
# would then have come from somewhere else than the system under test
MUST_STAY_ZERO = (
    ("tempo_search_scan_dispatches_total", {"mode": "host_fallback"}),
    ("tempo_search_device_faults_total", {}),
    ("tempo_search_device_breaker_transitions_total", {}),
    ("tempo_search_dispatch_lock_timeouts_total", {}),
)
DEVICE_MODES = ("batched", "coalesced", "single")


class Fatal(Exception):
    pass


class Say:
    """Every line names the platform, device kind and device count."""

    def __init__(self):
        self.tag = "platform=? kind=? n=?"

    def __call__(self, msg: str) -> None:
        print(f"[{self.tag}] {msg}", flush=True)


say = Say()


def load_reader(kind: str, name: str):
    """chipbench/<kind>/<name>.py, whatever characters the name has."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise Fatal(f"no reader {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 32), seed >> 32, stream])


# ---------------------------------------------------------------------------
# the plan: which requests, when


def build_requests(traffic: dict, manifest: dict, seed: int):
    """The cell's pool of concrete requests, drawn from the seed: the
    same number of each template for every seed. Returns (requests,
    ops); ops[k] has the op's `share` of the mix and `pool`, the indices
    of its requests."""
    rng = rng_for(seed, 1)
    requests, ops = [], []
    for k, op in enumerate(traffic["ops"]):
        mod = importlib.import_module(f"chipbench.ops.{op['op']}")
        pool = []
        for r in mod.build(op, manifest, rng):
            r.update(op=op["op"], name=op.get("name", op["op"]), op_index=k)
            pool.append(len(requests))
            requests.append(r)
        ops.append({"share": float(op["share"]), "pool": pool})
    return requests, ops


def op_sequence(op: dict, n: int, rng) -> list:
    """`n` request indices of one op: its pool in turn, from a start the
    seed draws."""
    pool = op["pool"]
    if not pool or n <= 0:
        return []
    start = int(rng.integers(0, len(pool)))
    return [pool[(start + j) % len(pool)] for j in range(n)]


def window_phase(traffic: dict, ops, seconds: float, rng,
                 rate: float | None = None) -> dict:
    """The schedule of one phase. Open loop: each op gets round(its share
    of the rate x seconds) arrivals, placed as a Poisson process places
    them given their number; every seed has the same counts, at other
    times and in another order. `rate` is the sweep's: it stands in for
    the mix's own."""
    total = sum(op["share"] for op in ops)
    if traffic["loop"] == "open":
        rate = float(traffic["rate"]) if rate is None else rate
        due = []
        for op in ops:
            n = int(round(rate * op["share"] / total * seconds))
            seq = op_sequence(op, n, rng)
            seq = [seq[i] for i in rng.permutation(len(seq))]
            times = np.sort(rng.random(len(seq)) * seconds)
            due.extend(zip(seq, times))
        due.sort(key=lambda it: it[1])
        return {"loop": "open", "threads": int(traffic.get("threads", 64)),
                "due": [[int(i), float(t)] for i, t in due]}
    clients = int(traffic["clients"])
    per = max(64, int(seconds * 100))
    order = []
    for _ in range(clients):
        counts = [int(per * op["share"] / total) for op in ops]
        seq = [i for op, c in zip(ops, counts)
               for i in op_sequence(op, c, rng)]
        order.append([seq[i] for i in rng.permutation(len(seq))])
    return {"loop": "closed", "clients": clients, "seconds": seconds,
            "order": order}


def burst_phase(traffic: dict, ops) -> dict:
    """Bursts released from a barrier, so that the fused (multi-query)
    kernel shapes are compiled in set-up: for each burst size, a burst
    of each op's templates alone and one of all together."""
    warm = traffic.get("warm", {})
    bursts = []
    pools = [op["pool"] for op in ops if op["pool"]]
    everything = [i for idx in pools for i in idx]
    for _ in range(int(warm.get("burst_repeats", 1))):
        for q in warm.get("bursts", []):
            for idx in pools:
                bursts.append([idx[j % len(idx)] for j in range(q)])
            bursts.append([everything[(j * 7) % len(everything)]
                           for j in range(q)])
    return {"loop": "burst", "bursts": bursts}


class Client:
    """Runs one phase in the child and reads its records back."""

    def __init__(self, base: str, requests: list, run_dir: str):
        self.base, self.run_dir, self.n = base, run_dir, 0
        self.wire = [{k: r[k] for k in ("method", "path", "headers", "body")
                      if k in r} for r in requests]
        self.summaries: list[dict] = []
        self.proc = None

    def stop(self) -> None:
        """No run leaves its load generator behind, however it ends."""
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    def start(self, phase: dict) -> tuple:
        self.n += 1
        plan = os.path.join(self.run_dir, f"plan{self.n}.json")
        out = os.path.join(self.run_dir, f"records{self.n}.jsonl")
        with open(plan, "w") as f:
            json.dump({"base": self.base, "requests": self.wire,
                       "phase": phase}, f)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "chipbench.client", plan, out], cwd=ROOT)
        return self.proc, out

    def finish(self, handle: tuple, timeout: float) -> list:
        proc, out = handle
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise Fatal("the load generator did not finish in time")
        if rc != 0:
            raise Fatal(f"the load generator exited {rc}")
        with open(out) as f:
            rows = [json.loads(line) for line in f]
        summary = rows.pop()
        if summary.get("jax_imported"):
            raise Fatal("the load generator imported JAX")
        self.summaries.append(summary)
        return rows

    def run(self, phase: dict, timeout: float = 1200.0) -> list:
        return self.finish(self.start(phase), timeout)


# ---------------------------------------------------------------------------
# the program's counters and spans


def scrape() -> dict:
    from chipbench.lib import parse_metrics
    from tempo_tpu.observability.metrics import REGISTRY

    return parse_metrics(REGISTRY.expose())


def span_dicts(spans) -> list:
    return [{
        "name": s.name, "start_ns": s.start_ns, "end_ns": s.end_ns,
        "span_id": s.context.span_id.hex(),
        "trace_id": s.context.trace_id.hex(),
        "parent_id": s.parent_span_id.hex() if s.parent_span_id else None,
        "attributes": dict(s.attributes)} for s in spans if s.end_ns]


def http_json(base: str, path: str, timeout: float = 30.0):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        body = r.read()
    try:
        return json.loads(body)
    except ValueError:
        return body.decode("utf-8", "replace")


def wait_for_blocks(base: str, want: dict, timeout: float) -> None:
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        try:
            have = http_json(base, "/status").get("blocks", {})
            if all(have.get(t, 0) >= n for t, n in want.items()):
                return
        except OSError:
            pass
        time.sleep(0.25)
    raise Fatal(f"the poll did not find {want} in {timeout:.0f}s")


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--scale", choices=("full", "tiny"), default="full",
                    help="tiny: the CPU rehearsal and chipbench/tests only")
    args = ap.parse_args(argv)
    result, code = run(args)
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


def run(args, hook=None, require_tpu=True):
    """Returns (result object or None, exit code). `hook(stage, state)`
    lets chipbench.sweep and the tests step in; the benchmark's own runs
    pass none. `require_tpu=False` is for chipbench/tests alone: it
    skips the look for a chip so that the rest of a run can be driven
    on the CPU, and returns the result instead of printing it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
    with open(os.path.join(ROOT, conf_entry["file"])) as f:
        config = json.load(f)
    traffic_dir = os.path.join(ROOT, os.path.dirname(
        os.path.dirname(conf_entry["file"])), "traffic")
    with open(os.path.join(traffic_dir, cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    if args.scale == "tiny":
        from chipbench.server import merge

        config = merge(config, config.get("tiny", {}))
        traffic = merge(traffic, traffic.get("tiny", {}))

    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    say.tag = f"platform={platform} kind={kind} n={len(devices)}"
    rehearsal = require_tpu and platform != "tpu"
    if rehearsal and args.scale != "tiny":
        say("no accelerator: refusing to measure (the CPU rehearsal is "
            "--scale tiny)")
        return None, EXIT_NO_CHIP
    if len(devices) < int(cell["chips"]):
        say(f"the cell needs {cell['chips']} chips, JAX sees {len(devices)}")
        return None, EXIT_NO_CHIP
    say(f"workload={cell['name']} config={cell['config']} "
        f"traffic={cell['traffic']} seed={args.seed} "
        f"seconds={args.seconds} trace={args.trace} scale={args.scale}")

    run_dir = tempfile.mkdtemp(prefix="chipbench-")
    state: dict = {"args": args, "cell": cell, "config": config,
                   "traffic": traffic, "run_dir": run_dir}
    server = client = None
    split: dict = {}
    try:
        t = time.perf_counter()
        lib_so = os.path.join(ROOT, "native", "libtempotpu.so")
        if not os.path.exists(lib_so):
            p = subprocess.run(["make", "-C", os.path.join(ROOT, "native"),
                                "libtempotpu.so"],
                               capture_output=True, text=True)
            if p.returncode != 0:
                raise Fatal("native build failed:\n" + p.stderr[-2000:])
        split["native"] = time.perf_counter() - t

        t = time.perf_counter()
        corpus = dict(config["corpus"], config_name=config["name"])
        gen = importlib.import_module(
            f"chipbench.generators.{corpus['generator']}")
        workers = max(2, min(12, (os.cpu_count() or 2) - 1))
        with ThreadPoolExecutor(workers) as pool:
            manifest = gen.generate(corpus, args.seed,
                                    os.path.join(run_dir, "blocks"), pool)
        split["corpus"] = time.perf_counter() - t
        say(f"corpus: generator={corpus['generator']} "
            f"blocks={manifest['blocks']} entries={manifest.get('entries')} "
            f"disk_bytes={manifest.get('disk_bytes')} corpus_cache=off "
            f"wall_s={split['corpus']:.1f}")
        state["manifest"] = manifest

        t = time.perf_counter()
        from chipbench.server import Server, render_config

        cfg_path = render_config(
            os.path.join(ROOT, "operations", "example-config.yaml"),
            config.get("yaml", {}), run_dir)
        server = Server(cfg_path)
        state["server"] = server
        say(f"server: base={server.base} compile_cache="
            f"{server.compile_cache or 'disabled'}")
        wait_for_blocks(server.base, manifest["blocks"], 300)
        split["server_and_poll"] = time.perf_counter() - t
        if hook:
            hook("server", state)

        warm_s = float(traffic.get("warm", {}).get("seconds", 10))
        if args.scale == "tiny":
            warm_s = min(warm_s, 2.0)
        requests, ops = build_requests(traffic, manifest, args.seed)
        state.update(requests=requests, ops=ops)
        client = Client(server.base, requests, run_dir)

        # every distinct request once, alone: stages the tenant, compiles
        # the single-query shapes, and gives the answers that are checked
        t = time.perf_counter()
        c0 = scrape()
        template_rows = client.run({
            "loop": "closed", "clients": 1, "seconds": 1e9,
            "order": [[i for op in ops for i in op["pool"]]]})
        split["templates"] = time.perf_counter() - t
        t = time.perf_counter()
        client.run(burst_phase(traffic, ops))
        split["bursts"] = time.perf_counter() - t
        t = time.perf_counter()
        warm_rows = client.run(window_phase(
            traffic, ops, warm_s, rng_for(args.seed, 2)))
        split["warm_traffic"] = time.perf_counter() - t
        c1 = scrape()
        from chipbench.lib import metric_sum

        miss = "tempo_search_jit_cache_events_total"
        stage = "tempo_search_dispatch_stage_seconds_sum"
        split["compile_inside_the_above"] = (
            metric_sum(c1, stage, stage="compile")
            - metric_sum(c0, stage, stage="compile"))
        say("set-up: jit keys compiled or replayed="
            f"{metric_sum(c1, miss, result='miss'):.0f} "
            f"from the persistent cache="
            f"{metric_sum(c1, miss, result='persisted'):.0f} "
            f"compile_stage_s={split['compile_inside_the_above']:.2f}")
        logical = metric_sum(c1, "tempo_search_hbm_cache_bytes")
        groups = metric_sum(c1, "tempo_search_batch_cache_events_total",
                            result="miss")
        say(f"staged logical bytes={logical:.0f} groups staged={groups:.0f} "
            f"peak_bytes_in_use={memory_peak(devices)}")
        if hook:
            hook("warm", dict(state, client=client, split=split))

        # ---- the measured window -------------------------------------
        collector = None
        if args.trace:
            from tempo_tpu.observability import tracing

            collector = tracing.CollectExporter()
            tracing.set_tracer(tracing.Tracer(
                tracing.SyncProcessor(collector)))
        phase = window_phase(traffic, ops, args.seconds,
                             rng_for(args.seed, 3))
        before = scrape()
        setup_s = time.perf_counter() - T_PROCESS
        t_window = time.perf_counter()
        handle = client.start(phase)
        trace_info = None
        if args.trace:
            trace_info = trace_window(run_dir, args.seconds)
        rows = client.finish(handle, timeout=args.seconds + 300)
        window_wall = time.perf_counter() - t_window
        after = scrape()
        if args.trace:
            from tempo_tpu.observability import tracing

            tracing.set_tracer(None)
        peak = memory_peak(devices)

        # ---- after the window: the comparison with the reference -------
        t = time.perf_counter()
        manifest["_pool"] = ThreadPoolExecutor(workers)
        checked = mismatches = 0
        details: list[str] = []
        mods = {op["op"]: importlib.import_module(f"chipbench.ops.{op['op']}")
                for op in traffic["ops"]}
        work: dict = {}
        for source, rws in (("set-up", template_rows), ("warm", warm_rows),
                            ("window", rows)):
            for r in rws:
                req = requests[r["i"]]
                ok, why = mods[req["op"]].check(req, r, manifest)
                checked += 1
                if not ok:
                    mismatches += 1
                    if len(details) < 5:
                        details.append(f"{source} {req['name']} "
                                       f"{req['path'][:120]}: {why}")
                if source == "window" and r["status"] == 200 and hasattr(
                        mods[req["op"]], "work"):
                    for k, v in mods[req["op"]].work(req, r).items():
                        work[k] = work.get(k, 0) + v
        manifest["_pool"].shutdown()
        reference_s = time.perf_counter() - t
        for d in details:
            say("MISMATCH " + d)

        final = scrape()
        zeros = {name + json.dumps(lab, sort_keys=True):
                 metric_sum(final, name, **lab) for name, lab in MUST_STAY_ZERO}
        on_device = sum(metric_sum(final,
                                   "tempo_search_scan_dispatches_total",
                                   mode=m) for m in DEVICE_MODES)
        jit_in_window = (metric_sum(after, miss, result="miss")
                         - metric_sum(before, miss, result="miss"))
        attempted = len(phase["due"]) if phase["loop"] == "open" else len(rows)
        failed = attempted - sum(1 for r in rows if r["status"] == 200)
        last = client.summaries[-1]
        say(f"window: attempted={attempted} failed={failed} "
            f"wall_s={window_wall:.2f} generator late median_s="
            f"{last['late_median_s']:.4f} max_s={last['late_max_s']:.4f}")
        say(f"jit misses inside the window={jit_in_window:.0f} "
            "(expected 0)")
        say(f"compared: answers checked={checked} mismatches={mismatches} "
            f"(limit 0); " + "; ".join(
                f"{k}={v:.0f} (limit 0)" for k, v in zeros.items())
            + f"; device dispatches={on_device:.0f} (limit > 0); "
            f"platform={platform} (must be tpu); reference_s="
            f"{reference_s:.1f} (not in setup_s)")
        say("set-up split s: " + " ".join(
            f"{k}={v:.2f}" for k, v in split.items())
            + f" total setup_s={setup_s:.2f}")
        correct = (mismatches == 0 and not rehearsal and on_device > 0
                   and all(v == 0 for v in zeros.values()))

        # ---- metrics ------------------------------------------------
        run_view = {
            "workload": cell["name"], "requests": requests, "records": rows,
            "seconds": args.seconds, "window_wall_s": window_wall,
            "setup_seconds": setup_s, "work": work,
            "counters": {"before": before, "after": after},
            "spans": span_dicts(collector.spans) if collector else [],
            "trace": trace_info, "device_kind": kind, "manifest": manifest,
            "config": config, "traffic": traffic,
        }
        state["run_view"] = run_view
        metrics = {}
        group = "per_layer" if args.trace else "end_to_end"
        folder = "layers" if args.trace else "metrics"
        for m in bench[group]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            value = load_reader(folder, m["name"]).compute(run_view)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        sizes = {}
        for r in rows:
            sizes[requests[r["i"]]["op"]] = sizes.get(
                requests[r["i"]]["op"], 0) + 1
        say(f"samples per op: {json.dumps(sizes, sort_keys=True)}")
        from chipbench.lib import latencies_ms, percentile

        for op in sorted(sizes):
            v = latencies_ms(run_view, op)
            say(f"latency from due, ms, op={op}: n={len(v)} "
                f"mean={sum(v) / len(v):.3f} " + " ".join(
                    f"p{q}={percentile(v, q):.3f}"
                    for q in (50, 90, 95, 99, 100)))

        device = {"platform": platform, "kind": kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        result = {"correct": bool(correct), "attempted": int(attempted),
                  "failed": int(failed), "metrics": metrics, "device": device}
        if trace_info is not None:
            device["busy_s"] = trace_info["busy_ns"] / 1e9
            device["window_s"] = trace_info["window_ns"] / 1e9
            result["breakdown"] = {
                "device_ops": [[n, ns / 1e9]
                               for n, ns in trace_info["ops_ns"][:10]],
                "idle_gaps": trace_info["idle_by_span"](run_view["spans"]),
            }
        if hook:
            hook("done", dict(state, result=result))
        if rehearsal:
            say("REHEARSAL on %s, not a measurement; it produced: %s" % (
                platform, " ".join(sorted(metrics))))
            return None, EXIT_REHEARSAL
        return result, 0
    except Fatal as e:
        say(f"FATAL {e}")
        return None, 1
    finally:
        if client is not None:
            client.stop()
        if server is not None:
            try:
                server.stop()
            except Exception as e:  # noqa: BLE001 — report, keep cleaning up
                say(f"server stop: {type(e).__name__}: {e}")
        shutil.rmtree(run_dir, ignore_errors=True)


def trace_window(run_dir: str, seconds: float) -> dict:
    """Wrap TRACE_SECONDS in the middle of the window in the profiler and
    reduce what it wrote. Runs on the main thread while the child sends."""
    import jax

    from chipbench import xplane

    trace_dir = os.path.join(run_dir, "trace")
    span = min(TRACE_SECONDS, seconds / 2)
    time.sleep(max(0.0, (seconds - span) / 2))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(xplane.SYNC_BEGIN):
        wall_ns = time.time_ns()
    time.sleep(span)
    with jax.profiler.TraceAnnotation(xplane.SYNC_END):
        pass
    jax.profiler.stop_trace()
    path = xplane.find_trace(trace_dir)
    if path is None:
        raise Fatal("the profiler wrote no trace")
    reduced = xplane.reduce(xplane.load(path))
    zero_wall = wall_ns - reduced["begin_ns"]
    reduced["idle_by_span"] = lambda spans: xplane.attribute_gaps(
        xplane.idle_gaps(reduced), spans, zero_wall)
    return reduced


if __name__ == "__main__":
    sys.exit(main())
