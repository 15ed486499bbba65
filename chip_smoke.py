#!/usr/bin/env python3
"""chip_smoke.py: the served search path, end to end, on the attached chip.

The quickest proof that the system still starts, stages, compiles and
answers on a TPU. One command, run from the root of a checkout:

    python3 chip_smoke.py                       # the real size, on a chip
    JAX_PLATFORMS=cpu python3 chip_smoke.py --blocks 6 \\
        --entries-per-block 4096 --push-traces 300   # dry run, exits 1

This process is a launcher and an HTTP client. It never initialises a
JAX backend: the server it starts (`python -m tempo_tpu.cli.main
-config.file=... -target=all`, as README "Run" does) is the only process
that touches the chip, and the second server starts only after the first
has exited.

What it drives, in order:

  build     `make -C native clean libtempotpu.so` (the .so is git-ignored)
  corpus    a bulk tenant of `--blocks` x `--entries-per-block` search
            entries (build_corpus: 1024-entry pages, 4 tags per
            entry), written to a local backend before the
            server starts and found by its poll. While it is written a
            plain numpy scan of the same columns answers every query
            below: that is the reference.
  queries   single tag; tag AND tag AND minDuration; a substring tag;
            duration only; a time window; a value in no dictionary.
            Each HTTP answer must equal the reference: inspectedTraces,
            the match set when it fits the limit, the top-`limit` start
            times otherwise.
  write     a second tenant receives `--push-traces` traces by
            POST /v1/traces; after /flush and a poll every acknowledged
            trace must come back by GET /api/traces/{id} and by a tag
            search.
  device    /status and /metrics must show a TPU, device dispatches, and
            no host fallback, device fault or breaker transition over
            the whole run, cold start included.
  restart   SIGTERM (exit 0 after the shutdown flush), a second server
            on the same directories, one query repeated: same answer,
            compiles replayed from the persistent cache.

Any failed check makes the exit code 1 and suppresses the result line.
A run whose server is not on a TPU never exits 0, whatever the size.
On success the last line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Every other line is a fact about this run (sizes, bytes, seconds on this
process's clock), not a benchmark metric.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.parse
import urllib.request
import uuid
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

BULK_TENANT = "smoke-bulk"
WRITE_TENANT = "smoke-write"
PAGE_ENTRIES = 1024          # build_corpus / scale_large_blocks geometry
EXHAUSTIVE_TAG = "x-dbg-exhaustive"   # search/pipeline.EXHAUSTIVE_SEARCH_TAG
# tempo_search_scan_dispatches_total modes that are batcher launches on
# the device (the ingester's one-block leg among them: a one-block
# batch; "host_fallback" is the CPU route; a mesh launch counts as batched/coalesced here and shows
# as mode="mesh" in the dispatch profiler's stage histogram)
DEVICE_MODES = ("batched", "coalesced")
WRITE_GROUP = 100            # pushed traces per tag-search group (< top_k)

# What the server's config changes from the shipped example
# (operations/example-config.yaml); everything else is the default,
# the 30 s dispatch watchdog and the 4 GB HBM budget included.
CONFIG_TEMPLATE = """\
server:
  http_port: {http_port}
  grpc_port: {grpc_port}
multitenancy_enabled: true
storage:
  backend: local
  local:
    path: {run_dir}/blocks
  wal_dir: {run_dir}/wal
ingester:
  n_ingesters: 3
  replication_factor: 2
compactor:
  # the bulk tenant's blocks carry search containers only (build_corpus);
  # compacting them is ROADMAP R3's cell, not this smoke
  tick_s: 86400
"""


class Checks:
    """Every comparison the smoke makes. A failed one is printed at once
    and fails the run at the end; the run goes on so one report shows
    everything that is wrong."""

    def __init__(self) -> None:
        self.failed: list[str] = []
        self.passed = 0

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        if ok:
            self.passed += 1
        else:
            self.failed.append(name)
            print(f"CHECK FAILED {name}: {detail}", flush=True)
        return ok


class Fatal(Exception):
    """A step without which nothing after it means anything."""


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


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# the bulk corpus and its reference


def block_trace_ids(block: int, n_pages: int) -> np.ndarray:
    """uint8 [P, E, 16]: big-endian block index, big-endian flat entry
    index, then a fixed tag. Unique across the tenant (results dedupe by
    trace id) and invertible, so an answer's id names its entry."""
    ids = np.zeros((n_pages, PAGE_ENTRIES, 16), dtype=np.uint8)
    ids[:, :, 0:4] = np.frombuffer(
        np.array([block], dtype=">u4").tobytes(), dtype=np.uint8)
    flat = np.arange(n_pages * PAGE_ENTRIES, dtype=">u4")
    ids[:, :, 4:8] = flat.view(np.uint8).reshape(n_pages, PAGE_ENTRIES, 4)
    ids[:, :, 8:16] = np.frombuffer(b"chipsmok", dtype=np.uint8)
    return ids


def entry_key(block, flat):
    return (np.asarray(block, dtype=np.int64) << 32) | np.asarray(
        flat, dtype=np.int64)


def key_of_trace_id(hex_id: str) -> int:
    raw = bytes.fromhex(hex_id.rjust(32, "0"))
    if raw[8:16] != b"chipsmok":
        return -1
    return (int.from_bytes(raw[0:4], "big") << 32) | int.from_bytes(
        raw[4:8], "big")


class Query:
    """One search request and, accumulated block by block while the
    corpus is written, its reference answer."""

    def __init__(self, name: str, tags: dict | None = None,
                 min_ms: int = 0, max_ms: int = 0, start: int = 0,
                 end: int = 0, limit: int = 20, exhaustive: bool = False):
        self.name = name
        self.tags = dict(tags or {})
        self.min_ms, self.max_ms = min_ms, max_ms
        self.start, self.end = start, end
        self.limit = limit
        self.exhaustive = exhaustive
        self.inspected = 0
        self.skipped_blocks = 0
        self._keys: list[np.ndarray] = []
        self._starts: list[np.ndarray] = []
        self._durs: list[np.ndarray] = []

    def params(self) -> dict:
        tags = dict(self.tags)
        if self.exhaustive:
            tags[EXHAUSTIVE_TAG] = "1"
        q = {"limit": str(self.limit)}
        if tags:
            q["tags"] = " ".join(f"{k}={v}" for k, v in sorted(tags.items()))
        if self.min_ms:
            q["minDuration"] = f"{self.min_ms}ms"
        if self.max_ms:
            q["maxDuration"] = f"{self.max_ms}ms"
        if self.start:
            q["start"] = str(self.start)
        if self.end:
            q["end"] = str(self.end)
        return q

    def scan_block(self, block: int, pages) -> None:
        """The plain reference: exact key, substring value, any kv slot;
        duration and window bounds inclusive. A block none of whose
        dictionary values can satisfy some term is skipped and its
        entries are not inspected, unless the request is exhaustive."""
        mask = pages.entry_valid.copy()
        prunable = False
        for k, needle in self.tags.items():
            kid = pages.key_dict.index(k) if k in pages.key_dict else -1
            vids = np.array([i for i, v in enumerate(pages.val_dict)
                             if needle in v], dtype=np.int32)
            if kid < 0 or not len(vids):
                prunable = True
            hit = (pages.kv_key == kid) & np.isin(pages.kv_val, vids)
            mask &= hit.any(axis=-1)
        if prunable and not self.exhaustive:
            self.skipped_blocks += 1
            return
        self.inspected += pages.n_entries
        if self.min_ms:
            mask &= pages.entry_dur >= self.min_ms
        if self.max_ms:
            mask &= pages.entry_dur <= self.max_ms
        if self.start:
            mask &= pages.entry_end >= self.start
        if self.end:
            mask &= pages.entry_start <= self.end
        flat = np.flatnonzero(mask.reshape(-1))
        self._keys.append(entry_key(block, flat))
        self._starts.append(pages.entry_start.reshape(-1)[flat])
        self._durs.append(pages.entry_dur.reshape(-1)[flat])

    def seal(self) -> None:
        cat = lambda xs, dt: (np.concatenate(xs) if xs  # noqa: E731
                              else np.zeros(0, dtype=dt))
        keys = cat(self._keys, np.int64)
        order = np.argsort(keys)
        self.keys = keys[order]
        self.starts = cat(self._starts, np.uint32)[order]
        self.durs = cat(self._durs, np.uint32)[order]
        self._keys = self._starts = self._durs = []

    @property
    def matches(self) -> int:
        return int(self.keys.shape[0])

    @property
    def deterministic(self) -> bool:
        """Whether the engine's answer is a function of the data alone.
        Once `limit` results are in hand the batcher stops dispatching
        and the answer depends on which groups ran first; a request that
        cannot fill its limit, or an exhaustive one, scans everything."""
        return self.exhaustive or self.matches < self.limit


def bulk_queries(time_base: int) -> list[Query]:
    svc = {"service.name": "svc-007"}
    return [
        Query("single-tag", svc, limit=20),
        Query("single-tag-exhaustive", svc, limit=20, exhaustive=True),
        Query("and-tags-minduration",
              {**svc, "http.status_code": "500"}, min_ms=59_990, limit=100),
        Query("substring-tag-and-tag",
              {"service.name": "svc-00", "region": "eu-west-1"},
              min_ms=59_990, limit=1000),
        Query("duration-only", min_ms=59_999, limit=1000),
        Query("time-window", {**svc, "http.status_code": "500"},
              start=time_base + 3600, end=time_base + 3660, limit=1000),
        Query("absent-value", {"service.name": "no-such-service"}, limit=20),
    ]


def write_bulk_corpus(run_dir: str, n_blocks: int, entries_per_block: int,
                      seed: int, queries: list[Query]) -> dict:
    """Write the bulk tenant's blocks (search container + header + meta)
    and answer every query by the
    reference scan on the way."""
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.backend.types import (
        BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER,
    )
    from tempo_tpu.encoding.v2.compression import compress

    be = LocalBackend(os.path.join(run_dir, "blocks"))
    n_pages = -(-entries_per_block // PAGE_ENTRIES)

    def one(i: int):
        pages = build_corpus(entries_per_block, E=PAGE_ENTRIES,
                             seed=seed + i)
        pages.trace_ids = block_trace_ids(i, n_pages)
        blob = compress(pages.to_bytes(), "zstd")
        hdr = dict(pages.header)
        hdr["encoding"] = "zstd"
        hdr["compressed_size"] = len(blob)
        # block ids from the seed too: the batcher cuts groups by a hash
        # of the ids, and the blocks-per-group counts are jit shapes
        m = BlockMeta(tenant_id=BULK_TENANT, encoding="zstd",
                      block_id=str(uuid.UUID(hashlib.md5(
                          f"chip-smoke/{seed}/{i}".encode()).hexdigest())))
        m.search_pages = hdr["n_pages"]
        m.search_size = len(blob)
        m.search_entries_per_page = hdr["entries_per_page"]
        m.search_kv_per_entry = hdr["kv_per_entry"]
        m.total_objects = hdr["n_entries"]
        be.write(BULK_TENANT, m.block_id, NAME_SEARCH, blob)
        be.write(BULK_TENANT, m.block_id, NAME_SEARCH_HEADER,
                 json.dumps(hdr).encode())
        be.write_block_meta(m)
        return i, pages, len(blob)

    disk = 0
    with ThreadPoolExecutor(max(2, min(8, os.cpu_count() or 2))) as ex:
        # the reference scans on this thread while the pool builds and
        # compresses ahead; results arrive in block order
        for i, pages, nbytes in ex.map(one, range(n_blocks)):
            disk += nbytes
            for q in queries:
                q.scan_block(i, pages)
    for q in queries:
        q.seal()
    return {"blocks": n_blocks, "entries": n_blocks * entries_per_block,
            "pages": n_blocks * n_pages, "disk_bytes": disk}


# ---------------------------------------------------------------------------
# the server and its HTTP surface


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def assert_jax_untouched() -> None:
    """A parent that has touched JAX holds the chip, and the server then
    fails or hangs. tempo_tpu imports pull jax in; importing is fine,
    initialising a backend is not."""
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is not None and xb._backends:
        raise Fatal("the launcher initialised a JAX backend: "
                    f"{sorted(xb._backends)}")


class Server:
    def __init__(self, name: str, run_dir: str, cfg_path: str,
                 http_port: int):
        self.name = name
        self.base = f"http://127.0.0.1:{http_port}"
        self.log_path = os.path.join(run_dir, f"{name}.log")
        self.cfg_path = cfg_path
        self.proc: subprocess.Popen | None = None

    def start(self) -> None:
        assert_jax_untouched()
        env = dict(os.environ, PYTHONUNBUFFERED="1")
        self._log = open(self.log_path, "wb")
        self.t_start = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "tempo_tpu.cli.main",
             f"-config.file={self.cfg_path}", "-target=all"],
            cwd=HERE, env=env, stdout=self._log, stderr=subprocess.STDOUT)

    def request(self, method: str, path: str, params: dict | None = None,
                body: bytes | None = None, headers: dict | None = None,
                timeout: float = 600.0) -> tuple[int, bytes]:
        url = self.base + path
        if params:
            url += "?" + urllib.parse.urlencode(params)
        req = urllib.request.Request(url, data=body, method=method,
                                     headers=headers or {})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def get_json(self, path: str, params: dict | None = None,
                 tenant: str | None = None, timeout: float = 600.0):
        headers = {"X-Scope-OrgID": tenant} if tenant else {}
        code, body = self.request("GET", path, params, headers=headers,
                                  timeout=timeout)
        try:
            return code, json.loads(body)
        except ValueError:
            return code, {"_raw": body[:500].decode("utf-8", "replace")}

    def wait_until(self, what: str, cond, timeout: float):
        """Poll `cond()` (truthy = done) while the server is alive."""
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            if self.proc.poll() is not None:
                raise Fatal(f"{self.name} exited rc={self.proc.returncode} "
                            f"while waiting for {what}")
            try:
                got = cond()
                if got:
                    return got
            except (urllib.error.URLError, ConnectionError, OSError):
                pass
            time.sleep(0.5)
        raise Fatal(f"{self.name}: {what} not reached in {timeout:.0f}s")

    def wait_ready(self, blocks: dict, timeout: float) -> dict:
        """Ready, and the reader's poll has found `blocks` (tenant ->
        count). Returns /status."""
        def cond():
            code, _ = self.request("GET", "/ready", timeout=5)
            if code != 200:
                return None
            code, st = self.get_json("/status", timeout=30)
            have = st.get("blocks", {}) if code == 200 else {}
            if all(have.get(t, 0) == n for t, n in blocks.items()):
                return st
            return None
        return self.wait_until(f"ready with blocks {blocks}", cond, timeout)

    def stop(self, timeout: float = 180.0) -> int | None:
        """SIGTERM and wait; SIGKILL past the timeout. Returns the exit
        code (None if it had to be killed)."""
        if self.proc is None:
            return None
        rc = self.proc.poll()
        if rc is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                rc = self.proc.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
                rc = None
        self._log.close()
        return rc

    def log_text(self) -> str:
        with open(self.log_path, "rb") as f:
            return f.read().decode("utf-8", "replace")


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {name: {label-string: value}}."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = re.match(r"([A-Za-z_:][\w:]*)(\{[^}]*\})?\s+(\S+)", line)
        if not m:
            continue
        try:
            v = float(m.group(3))
        except ValueError:
            continue
        out.setdefault(m.group(1), {})[m.group(2) or ""] = v
    return out


def metric_sum(metrics: dict, name: str, **labels) -> float:
    want = [f'{k}="{v}"' for k, v in labels.items()]
    return sum(v for lab, v in metrics.get(name, {}).items()
               if all(w in lab for w in want))


def scrape(srv: Server) -> dict:
    code, body = srv.request("GET", "/metrics", timeout=60)
    if code != 200:
        raise Fatal(f"/metrics answered {code}")
    return parse_metrics(body.decode("utf-8", "replace"))


# ---------------------------------------------------------------------------
# phases


def build_native() -> None:
    p = subprocess.run(["make", "-C", os.path.join(HERE, "native"),
                        "clean", "libtempotpu.so"],
                       capture_output=True, text=True)
    if p.returncode != 0:
        raise Fatal("native build failed:\n" + p.stdout[-2000:]
                    + p.stderr[-2000:])


def canonical(doc: dict) -> dict:
    """The part of a search answer that is a function of the data."""
    m = doc.get("metrics", {})
    return {
        "traces": sorted(
            (t.get("traceId", ""), t.get("startTimeUnixNano", ""),
             t.get("durationMs", 0)) for t in doc.get("traces", [])),
        "inspectedTraces": m.get("inspectedTraces", 0),
        "inspectedBlocks": m.get("inspectedBlocks", 0),
        "skippedBlocks": m.get("skippedBlocks", 0),
    }


def run_bulk_query(srv: Server, q: Query, c: Checks, n_blocks: int) -> dict:
    t0 = time.perf_counter()
    code, doc = srv.get_json("/api/search", q.params(), tenant=BULK_TENANT)
    wall = time.perf_counter() - t0
    name = f"query[{q.name}]"
    if not c.check(f"{name}.http", code == 200, f"HTTP {code}: {doc}"):
        return {}
    traces = doc.get("traces", [])
    m = doc.get("metrics", {})
    inspected = int(m.get("inspectedTraces", 0))
    got_keys = np.array([key_of_trace_id(t.get("traceId", ""))
                         for t in traces], dtype=np.int64)
    got_starts = sorted(
        (int(t.get("startTimeUnixNano", 0)) // 1_000_000_000
         for t in traces), reverse=True)

    # every returned trace is a reference match, rendered with the
    # reference's start and duration
    pos = np.searchsorted(q.keys, got_keys)
    pos = np.minimum(pos, max(0, q.matches - 1))
    known = (q.keys[pos] == got_keys) if q.matches else np.zeros(
        len(got_keys), dtype=bool)
    c.check(f"{name}.results-are-matches", bool(known.all()),
            f"{int((~known).sum())} of {len(traces)} returned traces are "
            "not reference matches")
    if len(traces) and known.all():
        rendered = all(
            int(t.get("startTimeUnixNano", 0)) // 1_000_000_000
            == int(q.starts[p])
            and int(t.get("durationMs", 0)) == int(q.durs[p])
            for t, p in zip(traces, pos))
        c.check(f"{name}.rendering", rendered,
                "startTimeUnixNano/durationMs differ from the columns")
    c.check(f"{name}.no-duplicates", len(set(got_keys.tolist()))
            == len(traces), "a trace id came back twice")

    if q.deterministic:
        c.check(f"{name}.inspectedTraces", inspected == q.inspected,
                f"engine {inspected} != reference {q.inspected}")
        if q.matches <= q.limit:
            c.check(f"{name}.match-set",
                    sorted(got_keys.tolist()) == q.keys.tolist(),
                    f"engine returned {len(traces)} traces, reference has "
                    f"{q.matches} matches")
        else:
            want = np.sort(q.starts)[::-1][:q.limit].tolist()
            c.check(f"{name}.top-limit-starts", got_starts == want,
                    f"engine {got_starts[:5]}.. != reference {want[:5]}..")
    else:
        # the limit can be met before every group ran: the answer is any
        # `limit` true matches over however much was scanned
        c.check(f"{name}.limit-filled", len(traces) == q.limit,
                f"{len(traces)} results for limit {q.limit} with "
                f"{q.matches} reference matches")
        c.check(f"{name}.inspectedTraces-bound",
                0 < inspected <= q.inspected,
                f"engine {inspected}, reference total {q.inspected}")
    if q.inspected:
        c.check(f"{name}.inspectedBytesDevice",
                int(m.get("inspectedBytesDevice", 0)) > 0,
                f"metrics {m}")
    else:
        c.check(f"{name}.all-blocks-skipped",
                int(m.get("skippedBlocks", 0)) == n_blocks,
                f"metrics {m}")
    say(f"  {q.name:26s} wall_s={wall:8.3f} results={len(traces):4d} "
        f"ref_matches={q.matches:7d} inspectedTraces={inspected} "
        f"skippedBlocks={m.get('skippedBlocks', 0)} "
        f"{'exact' if q.deterministic else 'early-quit'}")
    return {"wall_s": wall, "canonical": canonical(doc)}


def make_push_traces(n: int, seed: int) -> list[dict]:
    """`n` small traces from the seed: one resource, a root span and a
    child. The root carries `smoke.group`, shared by WRITE_GROUP traces,
    which is what the tag search asks for."""
    from tempo_tpu import tempopb

    rng = np.random.default_rng(seed ^ 0x5EED)
    now_ns = int(time.time()) * 1_000_000_000
    out = []
    for i in range(n):
        tid = rng.bytes(16)
        rs = tempopb.ResourceSpans()
        kv = rs.resource.attributes.add()
        kv.key = "service.name"
        kv.value.string_value = f"smoke-svc-{i % 8}"
        ss = rs.scope_spans.add()
        span_ids = []
        start = now_ns - int(rng.integers(60, 600)) * 1_000_000_000
        for s in range(2):
            sp = ss.spans.add()
            sp.trace_id = tid
            sp.span_id = rng.bytes(8)
            span_ids.append(bytes(sp.span_id))
            if s:
                sp.parent_span_id = span_ids[0]
            sp.name = "GET /smoke" if s == 0 else "db.query"
            sp.start_time_unix_nano = start + s * 1_000_000
            sp.end_time_unix_nano = (start + int(rng.integers(5, 900))
                                     * 1_000_000)
            if s == 0:
                a = sp.attributes.add()
                a.key = "smoke.group"
                a.value.string_value = f"grp-{i // WRITE_GROUP:05d}"
        out.append({"id": tid, "group": i // WRITE_GROUP, "batch": rs,
                    "span_ids": sorted(span_ids)})
    return out


def write_path(srv: Server, c: Checks, n_traces: int, seed: int,
               poll_timeout: float) -> dict:
    from tempo_tpu import tempopb

    traces = make_push_traces(n_traces, seed)
    hdr = {"X-Scope-OrgID": WRITE_TENANT,
           "Content-Type": "application/x-protobuf"}
    acked = []
    t0 = time.perf_counter()
    for lo in range(0, len(traces), 50):
        chunk = traces[lo:lo + 50]
        req = tempopb.Trace()
        req.batches.extend(t["batch"] for t in chunk)
        code, body = srv.request("POST", "/v1/traces",
                                 body=req.SerializeToString(), headers=hdr)
        if c.check(f"push[{lo}].ack", code == 200, f"HTTP {code}: {body!r}"):
            acked.extend(chunk)
    push_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    code, doc = srv.get_json("/flush", tenant=WRITE_TENANT)
    c.check("flush.http", code == 200, f"HTTP {code}: {doc}")
    flushed = int(doc.get("completed_blocks", 0)) if code == 200 else 0
    c.check("flush.completed-blocks", flushed > 0, f"{doc}")
    flush_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    srv.wait_until(
        f"poll to show {flushed} {WRITE_TENANT} blocks",
        lambda: srv.get_json("/status", timeout=30)[1].get(
            "blocks", {}).get(WRITE_TENANT, 0) >= flushed,
        poll_timeout)
    poll_wait_s = time.perf_counter() - t0

    before = scrape(srv)

    def by_id(t) -> str:
        code, body = srv.request(
            "GET", f"/api/traces/{t['id'].hex()}",
            headers={"X-Scope-OrgID": WRITE_TENANT,
                     "Accept": "application/protobuf"})
        if code != 200:
            return f"HTTP {code}"
        got = tempopb.Trace()
        got.ParseFromString(body)
        ids = sorted(bytes(s.span_id) for b in got.batches
                     for ss in b.scope_spans for s in ss.spans
                     if bytes(s.trace_id) == t["id"])
        return "" if ids == t["span_ids"] else f"spans {len(ids)}"

    t0 = time.perf_counter()
    with ThreadPoolExecutor(8) as ex:
        bad = [(t["id"].hex(), r) for t, r in zip(acked, ex.map(by_id, acked))
               if r]
    c.check("readback.by-id", not bad,
            f"{len(bad)} of {len(acked)} acknowledged traces not read back "
            f"by id, e.g. {bad[:3]}")
    by_id_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    found: set = set()
    device_bytes_ok = True
    n_groups = -(-len(traces) // WRITE_GROUP) if traces else 0
    for g in range(n_groups):
        code, doc = srv.get_json(
            "/api/search",
            {"tags": f"smoke.group=grp-{g:05d}", "limit": "128"},
            tenant=WRITE_TENANT)
        if not c.check(f"readback.search[{g}].http", code == 200,
                       f"HTTP {code}: {doc}"):
            continue
        found.update(t.get("traceId", "").rjust(32, "0")
                     for t in doc.get("traces", []))
        device_bytes_ok &= int(doc.get("metrics", {}).get(
            "inspectedBytesDevice", 0)) > 0
    missing = [t["id"].hex() for t in acked if t["id"].hex() not in found]
    c.check("readback.by-search", not missing,
            f"{len(missing)} of {len(acked)} acknowledged traces not found "
            f"by tag search, e.g. {missing[:3]}")
    c.check("readback.search-no-strangers",
            found <= {t["id"].hex() for t in traces},
            "tag search returned trace ids that were never pushed")
    c.check("readback.search-inspectedBytesDevice", device_bytes_ok,
            "a write-tenant search reported no device bytes")
    after = scrape(srv)
    rose = sum(
        metric_sum(after, "tempo_search_scan_dispatches_total", mode=m)
        - metric_sum(before, "tempo_search_scan_dispatches_total", mode=m)
        for m in DEVICE_MODES)
    c.check("readback.search-on-device-leg", rose >= n_groups,
            f"{rose:.0f} device dispatches for {n_groups} tag searches")
    search_s = time.perf_counter() - t0
    say(f"  pushed={len(traces)} acked={len(acked)} flushed_blocks={flushed} "
        f"read_back_by_id={len(acked) - len(bad)} "
        f"found_by_search={len(acked) - len(missing)} "
        f"device_dispatches={rose:.0f}")
    say(f"  wall_s: push={push_s:.1f} flush={flush_s:.1f} "
        f"poll_wait={poll_wait_s:.1f} by_id={by_id_s:.1f} "
        f"search={search_s:.1f}")
    return {"flushed_blocks": flushed}


def device_checks(srv: Server, c: Checks) -> dict:
    code, st = srv.get_json("/status", timeout=60)
    if code != 200:
        raise Fatal(f"/status answered {code}: {st}")
    dev = st.get("device", {})
    build = st.get("build", {})
    metrics = scrape(srv)
    platform = dev.get("backend", "unknown")
    say(f"  platform={platform} device_kind={dev.get('device_kind')} "
        f"device_count={dev.get('device_count')} "
        f"native={build.get('native')} jax={build.get('jax')}")
    c.check("device.platform-is-tpu", platform == "tpu",
            f"the server is not on a TPU: platform={platform}")
    c.check("device.kind-and-count",
            bool(dev.get("device_kind")) and int(
                dev.get("device_count") or 0) > 0, f"{dev}")
    c.check("build.native-loaded", build.get("native") == "loaded",
            f"native={build.get('native')}")

    disp = metrics.get("tempo_search_scan_dispatches_total", {})
    say(f"  scan_dispatches={json.dumps(disp, sort_keys=True)}")
    on_device = sum(metric_sum(
        metrics, "tempo_search_scan_dispatches_total", mode=m)
        for m in DEVICE_MODES)
    c.check("device.dispatches", on_device > 0,
            f"no dispatch in a device mode: {disp}")
    n_dev = int(dev.get("device_count") or 0)
    if n_dev > 1:
        mesh = metric_sum(metrics,
                          "tempo_search_dispatch_stage_seconds_count",
                          mode="mesh")
        say(f"  mesh-mode stage observations={mesh:.0f}")
        c.check("device.mesh-mode", mesh > 0,
                f"{n_dev} devices but no dispatch in profiler mode mesh")
    for name, what in (
            ("tempo_search_device_faults_total", "device faults"),
            ("tempo_search_device_breaker_transitions_total",
             "breaker transitions"),
            ("tempo_search_dispatch_lock_timeouts_total",
             "dispatch_lock timeouts")):
        total = metric_sum(metrics, name)
        c.check(f"device.no-{what.replace(' ', '-')}", total == 0,
                f"{what}: {metrics.get(name)}")
    c.check("device.no-host-fallback", metric_sum(
        metrics, "tempo_search_scan_dispatches_total",
        mode="host_fallback") == 0, f"{disp}")
    c.check("device.breaker-closed",
            dev.get("breaker", {}).get("state") == "closed"
            and not dev.get("wedged"), f"{dev.get('breaker')}")

    logical = metric_sum(metrics, "tempo_search_hbm_cache_bytes")
    per_dev = dev.get("devices", [])
    in_use = [d.get("bytes_in_use") for d in per_dev]
    peak = [d.get("peak_bytes_in_use") for d in per_dev]
    say(f"  hbm: staged_logical_bytes={logical:.0f} "
        f"device_bytes_in_use={in_use} device_peak_bytes_in_use={peak} "
        f"bytes_limit={[d.get('bytes_limit') for d in per_dev]}")
    if all(isinstance(b, int) for b in in_use) and in_use and logical:
        say(f"  hbm: physical/logical = {sum(in_use) / logical:.2f} "
            "(sum of bytes_in_use over staged logical bytes)")
        if len(in_use) > 1:
            c.check("device.memory-balanced",
                    max(in_use) <= 1.5 * max(1, min(in_use)),
                    f"per-device bytes_in_use {in_use}")
    h2d = metrics.get("tempo_search_h2d_bytes_total", {}).get("", 0)
    say(f"  h2d_bytes_total={h2d:.0f} "
        f"jit_cache_events={json.dumps(metrics.get('tempo_search_jit_cache_events_total', {}), sort_keys=True)}")
    return {"platform": platform, "kind": dev.get("device_kind"),
            "count": n_dev}


def report_log_facts(srv: Server) -> None:
    """The startup line, every jit key compiled with its compile time,
    and anything the server logged at error level."""
    text = srv.log_text()
    for line in text.splitlines():
        if 'msg="runtime: ' in line:
            say("  " + line[line.index('msg="') + 5:].rstrip('"'))
    compiles = re.findall(
        r'msg="jit compile: mode=(\S+) compile_ms=(\S+) key=(.*)"', text)
    say(f"  jit keys compiled: {len(compiles)} "
        f"(sum compile_ms={sum(float(ms) for _, ms, _ in compiles):.0f})")
    for mode, ms, key in compiles:
        say(f"    {mode:10s} {float(ms):9.1f} ms  {key}")
    errors = [ln for ln in text.splitlines() if "level=ERROR" in ln]
    for ln in errors[:20]:
        say("  server error: " + ln)


def main() -> int:
    ap = argparse.ArgumentParser(
        description="Drive the served search path once on the attached "
                    "TPU and check every answer (see the module docstring).")
    ap.add_argument("--seed", type=int, default=20260926)
    ap.add_argument("--blocks", type=int, default=600)
    ap.add_argument("--entries-per-block", type=int, default=65_536)
    ap.add_argument("--push-traces", type=int, default=3000)
    ap.add_argument("--workdir", default=None,
                    help="where blocks, WAL and server logs go "
                         "(default: a fresh temp dir, removed at exit)")
    ap.add_argument("--keep-logs", default=None, metavar="DIR",
                    help="copy the server logs here before exiting")
    args = ap.parse_args()

    c = Checks()
    t_run = time.perf_counter()
    own_dir = args.workdir is None
    run_dir = (tempfile.mkdtemp(prefix="chip-smoke-") if own_dir
               else os.path.abspath(args.workdir))
    os.makedirs(run_dir, exist_ok=True)
    servers: list[Server] = []
    device = None
    try:
        say("== build")
        build_native()
        say("  native/libtempotpu.so built")

        say("== corpus")
        t0 = time.perf_counter()
        queries = bulk_queries(time_base=1_600_000_000)
        corpus = write_bulk_corpus(run_dir, args.blocks,
                                   args.entries_per_block, args.seed,
                                   queries)
        say(f"  tenant={BULK_TENANT} blocks={corpus['blocks']} "
            f"entries={corpus['entries']} pages={corpus['pages']} "
            f"compressed_bytes={corpus['disk_bytes']} seed={args.seed} "
            f"wall_s={time.perf_counter() - t0:.1f}")

        http_port, grpc_port = free_port(), free_port()
        cfg_path = os.path.join(run_dir, "config.yaml")
        with open(cfg_path, "w") as f:
            f.write(CONFIG_TEMPLATE.format(
                http_port=http_port, grpc_port=grpc_port, run_dir=run_dir))

        say("== server 1")
        s1 = Server("server1", run_dir, cfg_path, http_port)
        servers.append(s1)
        s1.start()
        s1.wait_ready({BULK_TENANT: args.blocks}, timeout=300)
        say(f"  ready, poll found {args.blocks} blocks, "
            f"wall_s={time.perf_counter() - s1.t_start:.1f} since spawn")
        _, diff = s1.get_json("/status/config", {"mode": "diff"})
        say(f"  config diff from defaults: {json.dumps(diff, sort_keys=True)}")

        say("== queries (first one is cold: staging + compile)")
        answers = {}
        for q in queries:
            answers[q.name] = run_bulk_query(s1, q, c, args.blocks)
        digest = hashlib.sha256(json.dumps(
            {q.name: answers[q.name].get("canonical") for q in queries
             if q.deterministic}, sort_keys=True).encode()).hexdigest()
        say(f"  answers_sha256={digest} (deterministic queries; equal "
            "across runs with one seed and size)")

        say("== write path")
        write_path(s1, c, args.push_traces, args.seed, poll_timeout=120)

        say("== device")
        device = device_checks(s1, c)
        report_log_facts(s1)

        say("== restart")
        rc = s1.stop()
        c.check("restart.first-server-exit-0", rc == 0,
                f"server1 exit code {rc} after SIGTERM")
        s2 = Server("server2", run_dir, cfg_path, http_port)
        servers.append(s2)
        s2.start()
        s2.wait_ready({BULK_TENANT: args.blocks}, timeout=300)
        say(f"  server2 ready, wall_s="
            f"{time.perf_counter() - s2.t_start:.1f} since spawn")
        rq = next(q for q in queries if q.name == "and-tags-minduration")
        again = run_bulk_query(s2, rq, c, args.blocks)
        c.check("restart.same-answer",
                again.get("canonical") == answers[rq.name].get("canonical"),
                "the repeated query answered differently after restart")
        m2 = scrape(s2)
        persisted = metric_sum(m2, "tempo_search_jit_cache_events_total",
                               result="persisted")
        say(f"  jit_cache_events persisted={persisted:.0f}")
        c.check("restart.persisted-compile-cache-hits", persisted > 0,
                f"{m2.get('tempo_search_jit_cache_events_total')}")
        d2 = device_checks(s2, c)
        c.check("restart.same-device", d2 == device, f"{d2} != {device}")
        report_log_facts(s2)
        rc = s2.stop()
        c.check("restart.second-server-exit-0", rc == 0,
                f"server2 exit code {rc} after SIGTERM")
    except Fatal as e:
        c.check("fatal", False, str(e))
    finally:
        for s in servers:
            if s.proc is not None and s.proc.poll() is None:
                s.stop(timeout=30)
        if c.failed:
            for s in servers:
                if os.path.exists(s.log_path):
                    say(f"-- tail of {s.name}.log")
                    say("\n".join(s.log_text().splitlines()[-40:]))
        if args.keep_logs:
            os.makedirs(args.keep_logs, exist_ok=True)
            for s in servers:
                if os.path.exists(s.log_path):
                    shutil.copy(s.log_path, args.keep_logs)
        if own_dir:
            shutil.rmtree(run_dir, ignore_errors=True)

    say(f"== {c.passed} checks passed, {len(c.failed)} failed, "
        f"wall_s={time.perf_counter() - t_run:.1f}")
    if c.failed or device is None:
        say("FAILED: " + ", ".join(c.failed))
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
