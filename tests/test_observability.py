"""Observability: dispatch profiler, OpenMetrics exemplars, registry
thread-safety, self-trace health counters, and the metrics-catalog
drift guard.

The tentpole contracts pinned here:
  - every device dispatch mode (single / batched / coalesced / mesh /
    dict_probe) lands a stage breakdown in the profiler + histogram
  - `search_profiling_enabled: false` is a TRUE noop (shared immutable
    record, no clock reads)
  - exemplars appear in OpenMetrics output only under a sampled
    self-trace span, and parse per the OpenMetrics 1.0 text format
  - the docs metrics catalog cannot silently drift from the code
"""

from __future__ import annotations

import os
import random
import re
import threading
import time

import numpy as np
import pytest

from tempo_tpu import tempopb
from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import profile, tracing
from tempo_tpu.observability.metrics import (
    Counter,
    Gauge,
    Histogram,
    Registry,
)
from tempo_tpu.search import ColumnarPages, PageGeometry
from tempo_tpu.search.data import SearchData


# ---------------------------------------------------------------- helpers


def _corpus(n=120, seed=0):
    rng = random.Random(seed)
    entries = []
    for i in range(n):
        tid = (seed.to_bytes(2, "big") + i.to_bytes(4, "big")).rjust(16, b"\x00")
        sd = SearchData(trace_id=tid)
        sd.start_s = 1_600_000_000 + seed * 1_000_000 + i
        sd.end_s = sd.start_s + 5
        sd.dur_ms = rng.randint(1, 30_000)
        sd.root_service = f"svc-{rng.randrange(4)}"
        sd.root_name = "GET /"
        sd.kvs = {
            "service.name": {sd.root_service},
            "http.status_code": {str(rng.choice([200, 404, 500]))},
        }
        entries.append(sd)
    return entries


def _mk_req(tags=None, **kw):
    req = tempopb.SearchRequest()
    for k, v in (tags or {}).items():
        req.tags[k] = v
    for k, v in kw.items():
        setattr(req, k, v)
    return req


@pytest.fixture
def sync_tracer():
    """Install an always-sampling tracer with an inline exporter;
    restore the no-tracer state afterwards."""
    exporter = tracing.CollectExporter()
    tracer = tracing.Tracer(tracing.SyncProcessor(exporter),
                            sample_ratio=1.0)
    tracing.set_tracer(tracer)
    yield tracer, exporter
    tracing.set_tracer(None)


@pytest.fixture
def profiler_reset():
    """Fresh profiler state around a test, enabled, fence off."""
    profile.configure(enabled=True, fence=False)
    profile.PROFILER.reset()
    yield profile.PROFILER
    profile.configure(enabled=True, fence=False)
    profile.PROFILER.reset()


# -------------------------------------------------- registry thread-safety


def test_counter_gauge_value_reads_are_consistent():
    reg = Registry()
    c = Counter("t_total", "t", registry=reg)
    g = Gauge("t_g", "t", registry=reg)
    c.inc(2, tenant="a")
    g.set(7.5, tenant="a")
    assert c.value(tenant="a") == 2
    assert c.value(tenant="missing") == 0
    assert g.value(tenant="a") == 7.5


def test_registry_concurrent_inc_observe_expose_stress():
    """Writers on every metric kind race a reader calling expose() in
    both formats; totals must come out exact and no expose may raise
    (the satellite fix: value()/expose() take the series lock)."""
    reg = Registry()
    c = Counter("s_total", "stress counter", registry=reg)
    g = Gauge("s_gauge", "stress gauge", registry=reg)
    h = Histogram("s_hist", "stress histogram", registry=reg)
    N_THREADS, N_OPS = 8, 400
    stop = threading.Event()
    errors = []

    def writer(tid):
        try:
            for i in range(N_OPS):
                c.inc(shard=str(tid % 4))
                g.set(i, shard=str(tid % 4))
                h.observe(i / N_OPS, shard=str(tid % 4))
                c.value(shard=str(tid % 4))
        except Exception as e:  # noqa: BLE001 — collected for the assert
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                reg.expose()
                reg.expose(openmetrics=True)
                reg.samples()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(N_THREADS)]
    r = threading.Thread(target=reader)
    r.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    r.join()
    assert not errors
    total = sum(c.value(shard=str(s)) for s in range(4))
    assert total == N_THREADS * N_OPS
    # histogram observation counts add up exactly too
    assert sum(
        int(line.rsplit(" ", 1)[1])
        for line in reg.expose().splitlines()
        if line.startswith("s_hist_count")
    ) == N_THREADS * N_OPS


# ------------------------------------------------------ exemplars / formats

# OpenMetrics 1.0 exemplar on a bucket line:
#   name_bucket{labels} <int> # {trace_id="<hex>"} <value> <timestamp>
_EXEMPLAR_RE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)_bucket\{(?P<labels>[^}]*)\} '
    r'(?P<count>\d+) # \{trace_id="(?P<tid>[0-9a-f]{32})"\} '
    r'(?P<value>[0-9.eE+-]+) (?P<ts>[0-9]+(\.[0-9]+)?)$')


def test_histogram_exemplar_roundtrip_under_sampled_span(sync_tracer):
    tracer, _ = sync_tracer
    reg = Registry()
    h = Histogram("q_seconds", "q", registry=reg, buckets=(0.1, 1, 10))
    with tracer.start_span("scan") as span:
        h.observe(0.5, op="search")
        want_tid = span.context.trace_id.hex()

    om = reg.expose(openmetrics=True)
    assert om.endswith("# EOF\n")
    hits = [m for m in (
        _EXEMPLAR_RE.match(line) for line in om.splitlines()) if m]
    assert hits, f"no exemplar parsed from:\n{om}"
    m = hits[0]
    assert m.group("tid") == want_tid
    assert float(m.group("value")) == 0.5
    # the exemplar sits on the first bucket the value fell in (le=1)
    assert 'le="1.0"' in m.group("labels")
    # classic format stays exemplar-free and byte-compatible
    classic = reg.expose()
    assert "#" not in classic.replace("# HELP", "").replace("# TYPE", "")
    assert 'le="1"' in classic


def test_exemplar_absent_without_span_or_when_sampled_out():
    reg = Registry()
    h = Histogram("nospan_seconds", "q", registry=reg, buckets=(1,))
    h.observe(0.5)  # no tracer at all
    assert " # {" not in reg.expose(openmetrics=True)

    exporter = tracing.CollectExporter()
    tracer = tracing.Tracer(tracing.SyncProcessor(exporter),
                            sample_ratio=0.0)  # everything sampled OUT
    tracing.set_tracer(tracer)
    try:
        with tracer.start_span("scan"):
            h.observe(0.7)
    finally:
        tracing.set_tracer(None)
    assert " # {" not in reg.expose(openmetrics=True)


def test_openmetrics_counter_family_naming():
    """OpenMetrics names counter FAMILIES without the _total suffix in
    HELP/TYPE; the sample line keeps it. Classic format is unchanged."""
    reg = Registry()
    c = Counter("things_done_total", "things", registry=reg)
    c.inc(3)
    om = reg.expose(openmetrics=True)
    assert "# TYPE things_done counter" in om
    assert "things_done_total 3" in om
    classic = reg.expose()
    assert "# TYPE things_done_total counter" in classic


# ------------------------------------------------- self-trace health fixes


def test_selftrace_dropped_spans_counter(sync_tracer):
    tracer, _ = sync_tracer

    class _NeverExporter:
        def export(self, spans):
            pass

    bp = tracing.BatchProcessor(_NeverExporter(), max_queue=2,
                                interval_s=3600)
    try:
        # the metric is labelled by exporter class and is the single
        # source of truth — bp.dropped reads it back, no shadow count
        before = obs.selftrace_dropped_spans.value(
            exporter="_NeverExporter")
        for _ in range(5):
            with tracer.start_span("s") as sp:
                pass
            bp.on_end(sp)
        assert bp.dropped >= 3
        assert (obs.selftrace_dropped_spans.value(exporter="_NeverExporter")
                - before == bp.dropped)
    finally:
        bp.shutdown()


def test_selftrace_export_failure_counter(sync_tracer):
    tracer, _ = sync_tracer

    class _BoomExporter:
        def export(self, spans):
            raise RuntimeError("collector is down")

    bp = tracing.BatchProcessor(_BoomExporter(), interval_s=3600)
    try:
        before = obs.selftrace_export_failures.value(
            exporter="_BoomExporter")
        with tracer.start_span("s") as sp:
            pass
        bp.on_end(sp)
        bp.force_flush()  # swallows the raise, but must COUNT it
        assert obs.selftrace_export_failures.value(
            exporter="_BoomExporter") - before == 1
    finally:
        bp.shutdown()


# ---------------------------------------------------------- profiler core


def test_profiler_noop_path_is_shared_and_cheap(profiler_reset):
    prof = profiler_reset
    profile.configure(enabled=False)
    rec = profile.dispatch("single")
    assert rec is profile.NOOP_DISPATCH
    assert profile.dispatch("mesh") is rec  # shared, not allocated
    # the full call-site protocol is inert
    with rec:
        with rec.stage("build"):
            pass
        assert rec.compile_check(("k",)) is False
        rec.add_bytes(h2d=10).add_stage("h2d", 1.0).set(x=1).fence([])
    assert prof.snapshot()["dispatches"] == 0
    assert not prof.snapshot()["aggregates"]
    # observe_stage is a noop too
    profile.observe_stage("h2d", "single", 1.0, nbytes=1 << 30)
    assert prof.snapshot()["bytes"]["h2d"] == 0

    # overhead micro-check: 100k full noop call-sequences in well under
    # a second — the "true noop" contract at test granularity
    t0 = time.perf_counter()
    for _ in range(100_000):
        r = profile.dispatch("single")
        with r.stage("build"):
            pass
        r.close()
    assert time.perf_counter() - t0 < 1.0


def test_profiler_record_aggregation_and_ring(profiler_reset):
    prof = profiler_reset
    # the stage histogram is process-global: assert deltas, not totals
    om = obs.dispatch_stage_seconds
    key = om._key({"stage": "execute", "mode": "batched"})
    with om._lock:
        before = sum(om._counts.get(key, [0]))
    with profile.dispatch("batched") as rec:
        rec.add_stage("build", 0.002)
        with rec.stage("execute"):
            time.sleep(0.001)
        rec.add_bytes(h2d=100, d2h=50)
        assert rec.compile_check(("shape", 1)) is True   # first sight
    with profile.dispatch("batched") as rec2:
        assert rec2.compile_check(("shape", 1)) is False  # cached
        rec2.add_stage("execute", 0.001)
    snap = prof.snapshot()
    assert snap["dispatches"] == 2
    assert snap["jit_cache"] == {"hit": 1, "miss": 1}
    assert snap["bytes"] == {"h2d": 100, "d2h": 50}
    agg = snap["aggregates"]["batched"]
    assert agg["build"]["count"] == 1
    assert agg["execute"]["count"] == 2
    assert agg["execute"]["total_ms"] >= 1.0
    recent = snap["recent"]
    assert len(recent) == 2
    assert recent[0]["jit_cache"] == "miss"
    assert recent[1]["jit_cache"] == "hit"
    assert recent[0]["stages_ms"]["build"] == pytest.approx(2.0, abs=0.5)
    # metrics side: the stage histogram saw both dispatches
    with om._lock:
        assert sum(om._counts[key]) - before == 2

    prof.reset()
    assert prof.snapshot()["dispatches"] == 0


def test_profiler_stage_events_annotate_span(sync_tracer, profiler_reset):
    tracer, exporter = sync_tracer
    with tracer.start_span("query") as span:
        with profile.dispatch("single") as rec:
            with rec.stage("execute"):
                time.sleep(0.001)
            rec.add_stage("build", 0.003)   # a duration nobody stamped
        profile.observe_stage("d2h", "single", 0.001)
        # its caller wrote a span from the same stamps: no event beside it
        profile.observe_stage("d2h", "batched", 0.001, spanned=True)
    # out-of-record observations stay events; a timed stage is a child
    # span with the interval its timer observed, and a stage that was
    # only given a duration has no span (its edges were never read)
    assert [name for _ts, name, _attrs in span.events] == ["profile.stage"]
    kids = [s for s in exporter.spans if s.name.startswith("dispatch.")]
    assert [s.name for s in kids] == ["dispatch.execute"]
    ex = kids[0]
    assert ex.parent_span_id == span.context.span_id
    assert span.start_ns <= ex.start_ns < ex.end_ns <= span.end_ns
    assert (ex.end_ns - ex.start_ns) / 1e9 == rec.stages["execute"]
    # the stage timer stamped its thread's CPU clock beside the two
    # edges: the span says which thread, and how long it was on a core
    cpu = {k: ex.attributes.pop(k) for k in ("thread.id", "thread.cpu_ns")}
    assert ex.attributes == {"stage": "execute", "mode": "single"}
    assert cpu["thread.id"] == threading.get_ident()
    assert cpu["thread.cpu_ns"] >= 0


def test_profiler_ring_resize_and_bound(profiler_reset):
    prof = profiler_reset
    profile.configure(ring_size=4)
    try:
        for i in range(10):
            with profile.dispatch("single") as rec:
                rec.add_stage("build", 0.001 * (i + 1))
        assert len(prof.snapshot(recent=100)["recent"]) == 4
    finally:
        profile.configure(ring_size=256)


def test_fence_arrays_tolerates_host_values():
    profile.fence_arrays((1, None, np.zeros(2)))  # must not raise


# --------------------------------------- every dispatch mode is profiled


def _modes_seen():
    return set(profile.PROFILER.snapshot()["aggregates"])


def test_all_dispatch_modes_populate_profiler(profiler_reset):
    """Acceptance: /debug/profile and the stage histogram populated for
    batched (of one block and of several), coalesced, mesh AND
    dict_probe dispatches."""
    from tempo_tpu.parallel import make_mesh
    from tempo_tpu.search import dict_probe
    from tempo_tpu.search.multiblock import (
        MultiBlockEngine,
        compile_multi,
        stack_queries,
    )

    req = _mk_req({"service.name": "svc-1"}, limit=20)
    blocks = [ColumnarPages.build(_corpus(100, seed=s), PageGeometry(16, 8))
              for s in range(3)]

    # one block is a one-block batch: the same mode, none of its own
    mbe = MultiBlockEngine(top_k=64)
    mbe.scan(mbe.stage(blocks[:1]), compile_multi(blocks[:1], req))
    assert _modes_seen() == {"batched", "host_probe"}

    # batched (multi-block, one device)
    batch = mbe.stage(blocks)
    mq = compile_multi(blocks, req)
    mbe.scan(batch, mq)
    assert "batched" in _modes_seen()

    # coalesced (two stacked queries, one fused kernel)
    mq2 = compile_multi(blocks, _mk_req({"service.name": "svc-2"},
                                        limit=20))
    ccq = stack_queries([mq, mq2])
    out = mbe.coalesced_scan_async(batch, ccq, 64)
    from tempo_tpu.search.engine import fetch_scan_out

    fetch_scan_out(out)
    assert "coalesced" in _modes_seen()

    # mesh (8 virtual CPU devices, conftest)
    dist = MultiBlockEngine(top_k=64, mesh=make_mesh())
    dist.scan(dist.stage(blocks), mq)
    assert "mesh" in _modes_seen()

    # dict_probe kernel
    ddev = dict_probe.place_device_dict(
        dict_probe.pack_device_dict(blocks[0].val_dict))
    dict_probe.probe_value_hits(ddev, [b"svc-1"])
    assert "dict_probe" in _modes_seen()

    snap = profile.PROFILER.snapshot()
    for mode in ("batched", "coalesced", "mesh", "dict_probe"):
        stages = snap["aggregates"][mode]
        assert stages, f"mode {mode} has no stage aggregates"
        # every profiled dispatch timed its kernel call
        assert "compile" in stages or "execute" in stages or \
            "h2d" in stages
    # the histogram carries the same series
    exposed = obs.dispatch_stage_seconds.expose()
    for mode in ("batched", "coalesced", "mesh", "dict_probe"):
        assert f'mode="{mode}"' in exposed
    # jit-cache events observed for the fresh shapes
    assert snap["jit_cache"]["miss"] >= 4


def test_host_probe_mode_recorded(profiler_reset):
    """The host memmem prefilter (PR4's motivating cost) records under
    mode=host_probe so the stage histogram shows host vs device probe."""
    from tempo_tpu.search.pipeline import compile_query

    block = ColumnarPages.build(_corpus(60, seed=1), PageGeometry(16, 8))
    compile_query(block.key_dict, block.val_dict,
                  _mk_req({"service.name": "svc-1"}, limit=20))
    agg = profile.PROFILER.snapshot()["aggregates"]
    assert "host_probe" in agg
    assert agg["host_probe"]["build"]["count"] >= 1


def test_profiler_disabled_leaves_dispatch_paths_silent(profiler_reset):
    from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi

    profile.configure(enabled=False)
    block = ColumnarPages.build(_corpus(80, seed=2), PageGeometry(16, 8))
    eng = MultiBlockEngine(top_k=64)
    mq = compile_multi([block],
                       _mk_req({"service.name": "svc-1"}, limit=20))
    eng.scan(eng.stage([block]), mq)
    snap = profile.PROFILER.snapshot()
    assert snap["dispatches"] == 0
    assert not snap["aggregates"]


# ------------------------------------------------------ catalog drift guard


def test_metrics_catalog_complete():
    """Every metric name registered anywhere in tempo_tpu/ must appear
    in docs/observability.md — the catalog cannot silently drift.
    Thin wrapper over the analysis drift engine's "metric-names"
    catalog (tempo_tpu/analysis/drift.py; same invariant this test
    enforced with a hand-rolled regex walk before PR 10, incl. the
    >=30-names extractor sanity floor)."""
    from tempo_tpu.analysis.drift import catalog_findings

    findings = catalog_findings("metric-names")
    assert not findings, (
        "metrics missing from docs/observability.md catalog "
        "(add them to the table):\n"
        + "\n".join(f"{f.path}:{f.line}: {f.message}" for f in findings))
