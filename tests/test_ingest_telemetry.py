"""Write-path telemetry end-to-end: the push→searchable stage record,
freshness gauges, backlog visibility, the canary, the slow-flush log,
the WAL-replay metrics, and the telemetry-off noop contract
(observability/ingest_telemetry.py + the instrumented distributor /
ingester / poller / compactor sites)."""

import json
import logging
import threading
import time

import pytest

from tempo_tpu.modules import App, AppConfig
from tempo_tpu.observability import ingest_telemetry
from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability.ingest_telemetry import (
    TELEMETRY,
    IngestCanary,
)
from tempo_tpu.utils.ids import random_trace_id
from tempo_tpu.utils.test_data import make_trace


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Process-global sink: every test starts from a known config and
    leaves no pending flush→poll pairs for its neighbors."""
    ingest_telemetry.configure(enabled=True, slow_flush_log_s=30.0)
    TELEMETRY.reset()
    TELEMETRY.canary = None
    yield
    ingest_telemetry.configure(enabled=True, slow_flush_log_s=30.0)
    TELEMETRY.reset()
    TELEMETRY.canary = None


def _app(tmp_path, **kw):
    return App(AppConfig(wal_dir=str(tmp_path / "wal"), **kw))


def _now_batch(tag_value: str = ""):
    """One single-span trace stamped NOW (the freshness gauge derives
    from block end_times, so 2020-epoch test data would read as years
    of staleness)."""
    import os

    from tempo_tpu import tempopb

    rs = tempopb.ResourceSpans()
    kv = rs.resource.attributes.add()
    kv.key = "service.name"
    kv.value.string_value = "svc-now"
    ss = rs.scope_spans.add()
    span = ss.spans.add()
    span.trace_id = os.urandom(16)
    span.span_id = os.urandom(8)
    span.name = "op-now"
    now_ns = time.time_ns()
    span.start_time_unix_nano = now_ns - 5_000_000
    span.end_time_unix_nano = now_ns
    if tag_value:
        kv = span.attributes.add()
        kv.key = "probe.id"
        kv.value.string_value = tag_value
    return rs


def _stage_count(stage: str) -> int:
    h = obs.ingest_stage_seconds
    with h._lock:
        counts = h._counts.get((("stage", stage),))
        return sum(counts) if counts else 0


def _hist_count(hist, **labels) -> int:
    with hist._lock:
        counts = hist._counts.get(hist._key(labels))
        return sum(counts) if counts else 0


STAGES = ("push_ack", "live_cut", "block_cut", "flush", "flush_write",
          "poll_visible", "push_to_searchable")


# ---- the full pipeline record ----

def test_stage_histograms_populate_push_to_searchable(tmp_path):
    before = {s: _stage_count(s) for s in STAGES}
    flushes = _hist_count(obs.flush_duration_seconds, tenant="t1")
    app = _app(tmp_path)
    for _ in range(4):
        app.push("t1", [_now_batch()])
    app.flush_tick(force=True)
    app.poll_tick()
    # every stage of push -> cut -> complete -> flush -> poll observed
    for s in STAGES:
        assert _stage_count(s) > before[s], f"stage {s} not observed"
    assert _hist_count(obs.flush_duration_seconds, tenant="t1") > flushes
    # backlog gauges: everything flushed, nothing waiting
    assert obs.flush_queue_length.value(tenant="t1") == 0
    assert obs.oldest_unflushed.value(tenant="t1") == 0
    assert obs.blocklist_length.value(tenant="t1") >= 1


def test_freshness_gauge_small_after_poll_of_fresh_data(tmp_path):
    app = _app(tmp_path)
    app.push("fresh-t", [_now_batch()])
    app.flush_tick(force=True)
    app.poll_tick()
    # spans were stamped NOW: the polled freshness must be seconds, and
    # the gauge must have DECREASED from whatever staler state a prior
    # poll (other tests, earlier blocks) left behind
    v = obs.search_freshness.value(tenant="fresh-t")
    assert 0 <= v < 60
    # a later poll without new data ages the gauge monotonically
    time.sleep(0.02)
    app.poll_tick()
    assert obs.search_freshness.value(tenant="fresh-t") >= v


def test_oldest_unflushed_tracks_backlog_then_resets(tmp_path):
    app = _app(tmp_path)
    app.push("lag-t", [_now_batch()])
    ing = app.ingesters["ingester-0"]
    # sweep WITHOUT force: the trace stays live (idle < 10s) — the
    # backlog gauge must show its age (gauge precision is 1ms, so give
    # the trace measurable age first)
    time.sleep(0.02)
    ing.sweep()
    assert obs.flush_queue_length.value(tenant="lag-t") == 0
    lag = obs.oldest_unflushed.value(tenant="lag-t")
    assert 0 < lag < 60
    app.flush_tick(force=True)
    assert obs.oldest_unflushed.value(tenant="lag-t") == 0


def test_push_ack_not_recorded_when_disabled(tmp_path):
    app = _app(tmp_path, ingest_telemetry_enabled=False)
    before = {s: _stage_count(s) for s in STAGES}
    for _ in range(3):
        app.push("off-t", [_now_batch()])
    app.flush_tick(force=True)
    app.poll_tick()
    for s in STAGES:
        assert _stage_count(s) == before[s], f"stage {s} leaked while off"


def test_telemetry_off_is_byte_identical_on_the_wal(tmp_path):
    """The noop contract: identical pushes produce identical WAL bytes
    with telemetry on vs off."""

    def wal_bytes(enabled: bool, sub: str) -> bytes:
        ingest_telemetry.configure(enabled=enabled)
        app = App(AppConfig(wal_dir=str(tmp_path / sub),
                            ingest_telemetry_enabled=enabled))
        for i in range(6):
            tr = make_trace(bytes([i + 1]) * 16, seed=i)
            app.push("noop", list(tr.batches))
        inst = app.ingesters["ingester-0"].instance("noop")
        inst.cut_complete_traces(force=True)
        with open(inst.head.path, "rb") as f:
            head = f.read()
        with open(inst.head.path + ".search", "rb") as f:
            return head + b"\x00|\x00" + f.read()

    on = wal_bytes(True, "on")
    off = wal_bytes(False, "off")
    assert on == off
    assert len(on) > 100  # the comparison compared real data


# ---- flush failure / retry visibility ----

def test_flush_retry_counter_by_attempt_bucket(tmp_path, monkeypatch):
    app = _app(tmp_path)
    app.push("rt", [_now_batch()])
    ing = app.ingesters["ingester-0"]
    inst = ing.instance("rt")
    inst.cut_complete_traces(force=True)
    inst.cut_block_if_ready(force=True)
    before1 = obs.flush_retries.value(attempt="1")
    before2 = obs.flush_retries.value(attempt="2")
    boom = RuntimeError("backend down")
    monkeypatch.setattr(ing.db, "complete_block",
                        lambda *a, **k: (_ for _ in ()).throw(boom))
    with pytest.raises(RuntimeError):
        inst.complete_one(ignore_backoff=True)
    with pytest.raises(RuntimeError):
        inst.complete_one(ignore_backoff=True)
    assert obs.flush_retries.value(attempt="1") == before1 + 1
    assert obs.flush_retries.value(attempt="2") == before2 + 1
    # the block is still completing (not lost), and recovers
    monkeypatch.undo()
    assert inst.complete_one(ignore_backoff=True) is not None
    assert not inst.completing


def test_slow_flush_log_line_is_pure_json(tmp_path, caplog):
    before = obs.slow_flushes.value(tenant="slow-t")
    # threshold via the App config (App construction re-configures the
    # process sink, so a bare configure() before it would be undone)
    app = _app(tmp_path, ingest_slow_flush_log_s=1e-9)
    app.push("slow-t", [_now_batch()])
    with caplog.at_level(logging.WARNING, logger="tempo_tpu.slowflush"):
        app.flush_tick(force=True)
    lines = [r for r in caplog.records if r.name == "tempo_tpu.slowflush"]
    assert lines, "no slow-flush line emitted"
    doc = json.loads(lines[0].getMessage())
    assert doc["msg"] == "slow flush"
    assert doc["tenant"] == "slow-t"
    assert doc["duration_s"] >= 0
    assert doc["objects"] >= 1
    assert "block_id" in doc and "attempts" in doc
    assert obs.slow_flushes.value(tenant="slow-t") > before
    # the ring for /debug/ingest carries the same entry
    assert any(e["tenant"] == "slow-t"
               for e in TELEMETRY.debug_snapshot()["slow_flushes"])


# ---- WAL replay attribution ----

def test_wal_replay_is_timed_and_exported(tmp_path):
    app = _app(tmp_path)
    for i in range(3):
        tr = make_trace(random_trace_id(), seed=i)
        app.push("replay-t", list(tr.batches))
    inst = app.ingesters["ingester-0"].instance("replay-t")
    inst.cut_complete_traces(force=True)
    assert len(inst.head) > 0  # data sits in the WAL, unflushed
    # a new process over the same WAL dir replays it
    app2 = _app(tmp_path)
    ing2 = app2.ingesters["ingester-0"]
    assert ing2.replayed_blocks >= 1
    stats = ing2.db.wal.last_replay
    assert stats["blocks"] >= 1
    assert stats["bytes"] > 0
    assert stats["duration_s"] > 0
    assert obs.wal_replayed_blocks.value() >= 1
    assert obs.wal_replayed_bytes.value() > 0
    assert obs.wal_replay_seconds.value() > 0
    assert TELEMETRY.debug_snapshot()["wal_replay"]["blocks"] >= 1
    # replayed blocks flush on the next sweep
    assert len(app2.flush_tick(force=True)) >= 1


def test_replayed_backlog_ages_instead_of_reading_zero(tmp_path):
    """Replayed WAL blocks carry no push stamp — the oldest-unflushed
    gauge must fall back to their enqueue (replay) time so a wedged
    post-restart backlog ages instead of reporting 'fully flushed'
    (review r3)."""
    app = _app(tmp_path)
    app.push("rb-t", [_now_batch()])
    inst = app.ingesters["ingester-0"].instance("rb-t")
    inst.cut_complete_traces(force=True)
    app2 = _app(tmp_path)  # replays; nobody flushes (wedged restart)
    ing2 = app2.ingesters["ingester-0"]
    assert ing2.replayed_blocks >= 1
    time.sleep(0.02)
    ing2._publish_queue_state()
    assert obs.flush_queue_length.value(tenant="rb-t") >= 1
    assert obs.oldest_unflushed.value(tenant="rb-t") > 0


# ---- canary ----

def _ticking(app, stop, flush_every=0.05):
    def body():
        while not stop.wait(flush_every):
            app.flush_tick(force=True)
            app.poll_tick()
    t = threading.Thread(target=body, daemon=True)
    t.start()
    return t


def test_canary_round_trip_measures_freshness(tmp_path):
    app = _app(tmp_path)
    stop = threading.Event()
    t = _ticking(app, stop)
    try:
        can = IngestCanary(app.push, app.reader_db.search,
                           tenant="canary-ok", poll_step_s=0.02)
        f = can.probe_once(timeout_s=60.0)
    finally:
        stop.set()
        t.join(timeout=10.0)
    assert f is not None and f > 0
    assert can.failures == 0
    assert can.state()["last_freshness_s"] == round(f, 3)
    assert obs.canary_freshness.value() == round(f, 3)
    # the canary block went through the real pipeline: freshness gauge
    # exists for its tenant too
    assert obs.search_freshness.value(tenant="canary-ok") < 60


def test_canary_failure_counter_fires_when_pipeline_is_wedged(tmp_path):
    app = _app(tmp_path)  # nobody drives flush/poll: a wedged pipeline
    before = obs.canary_failures.value()
    can = IngestCanary(app.push, app.reader_db.search,
                       tenant="canary-wedge", poll_step_s=0.02)
    f = can.probe_once(timeout_s=0.3)
    assert f is None
    assert can.failures == 1
    assert obs.canary_failures.value() == before + 1
    assert "not searchable" in can.state()["last_error"]


def test_canary_lifecycle_via_app_config(tmp_path):
    app = _app(tmp_path, ingest_canary_enabled=True,
               ingest_canary_interval_s=3600.0)
    try:
        assert app.canary is not None
        assert TELEMETRY.canary is app.canary
        app.run_maintenance()
        assert app.canary.state()["running"]
    finally:
        app.shutdown()
    assert not app.canary.state()["running"]


# ---- surfaces ----

def test_status_and_debug_ingest_surfaces(tmp_path):
    from tempo_tpu.api.http import HTTPApi

    app = _app(tmp_path)
    app.push("surf-t", [_now_batch()])
    app.flush_tick(force=True)
    app.poll_tick()
    app.compaction_tick()
    api = HTTPApi(app)
    code, status = api.handle("GET", "/status", {}, {})
    assert code == 200
    blk = status["ingest"]
    assert "surf-t" in blk["freshness_seconds"]
    assert blk["oldest_unflushed_seconds"]["surf-t"] == 0
    assert blk["last_poll_age_s"] is not None
    code, dbg = api.handle("GET", "/debug/ingest", {}, {})
    assert code == 200
    json.dumps(dbg)  # a debug page must always be JSON-serializable
    assert dbg["enabled"] is True
    assert dbg["queues"]["surf-t"]["queue_length"] == 0
    assert dbg["last_flush"]["surf-t"]["objects"] >= 1
    assert dbg["last_poll"]["blocks"] >= 1
    # live view: this app runs ingesters in-process
    assert dbg["live"]["surf-t"]["live_traces"] == 0
    assert dbg["live"]["surf-t"]["recent_blocks"] >= 1


def test_compaction_backlog_and_run_metrics(tmp_path):
    app = _app(tmp_path)
    # two same-window blocks -> one compactable group
    for i in range(2):
        app.push("comp-t", [_now_batch()])
        app.flush_tick(force=True)
    app.poll_tick()
    runs_before = _hist_count(obs.compaction_duration_seconds)
    app.compaction_tick()
    assert _hist_count(obs.compaction_duration_seconds) > runs_before
    # backlog gauge was set (to the pre-run backlog) for the tenant
    assert obs.compaction_outstanding_bytes.value(tenant="comp-t") > 0


def test_freshness_gauge_removed_when_tenant_vanishes():
    """A tenant that disappears from a poll must STOP exporting its
    last freshness value — a frozen 'fresh' reading for a tenant whose
    searchable data is gone is worse than no series (review r1)."""
    from tempo_tpu.backend.types import BlockMeta

    m = BlockMeta(tenant_id="ghost-t", end_time=int(time.time()))
    TELEMETRY.record_poll(0.01, {"ghost-t": [m]})
    assert obs.search_freshness.value(tenant="ghost-t") < 60
    with obs.search_freshness._lock:
        assert (("tenant", "ghost-t"),) in obs.search_freshness._series
    TELEMETRY.record_poll(0.01, {})  # tenant gone from the next poll
    with obs.search_freshness._lock:
        assert (("tenant", "ghost-t"),) not in obs.search_freshness._series
    with obs.blocklist_length._lock:
        assert (("tenant", "ghost-t"),) not in obs.blocklist_length._series
    assert "ghost-t" not in TELEMETRY.status()["freshness_seconds"]


def test_blocklist_index_age_gauge(tmp_backend_dir, tmp_wal_dir):
    """A reader (non-builder) poller consuming a builder-written tenant
    index must export the index's age."""
    from tempo_tpu.backend import open_backend
    from tempo_tpu.db import TempoDB, TempoDBConfig

    backend = open_backend({"backend": "local",
                            "local": {"path": tmp_backend_dir}})
    writer = TempoDB(backend, tmp_wal_dir + "/w", TempoDBConfig())
    writer.write_block_direct(
        "idx-t", [(bytes([7]) * 16, b"obj-bytes", 10, 20)])
    writer.poll()  # builder: writes the tenant index
    reader = TempoDB(backend, tmp_wal_dir + "/r", TempoDBConfig(
        tenant_index_builder=False))
    reader.poll()
    assert obs.blocklist_index_age.value(tenant="idx-t") >= 0
    assert obs.blocklist_length.value(tenant="idx-t") == 1
