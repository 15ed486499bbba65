"""Test harness: force an 8-device virtual CPU platform before jax loads.

Multi-chip TPU hardware is not available in CI; all sharding/parallelism
tests run over a virtual 8-device CPU mesh, exactly as the driver's
dryrun_multichip does. This must run before any jax import anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never touch the real chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# the env vars above are for child processes and for a jax not yet
# imported; jax.config covers a plugin that imported jax before conftest
# ran, and an XLA_FLAGS that arrived with a different device count
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture
def tmp_backend_dir(tmp_path):
    d = tmp_path / "backend"
    d.mkdir()
    return str(d)


@pytest.fixture
def tmp_wal_dir(tmp_path):
    d = tmp_path / "wal"
    d.mkdir()
    return str(d)


class BatchScan:
    """One launch of the scan over `blocks` stacked as one batch, its
    outputs on the host. `mq` is None when the dictionary prefilter
    pruned every block (nothing launched; count 0)."""

    def __init__(self, engine, batch, mq, out=None):
        self.engine, self.batch, self.mq = engine, batch, mq
        self.count, self.inspected, self.scores, self.idx = (
            out if out is not None else (0, 0, None, None))

    @property
    def out(self):
        return self.count, self.inspected, self.scores, self.idx

    @property
    def metas(self):
        if self.mq is None:
            return []
        return self.engine.results(self.batch, self.mq, self.scores,
                                   self.idx)

    @property
    def trace_ids(self):
        """Every match among the top-k rows, whatever the request's
        limit, resolved through the batch's page -> block map."""
        if self.mq is None:
            return set()
        b = self.batch
        E = b.blocks[0].geometry.entries_per_page
        out = set()
        for s, i in zip(self.scores.tolist(), self.idx.tolist()):
            if s < 0:
                break
            p, e = divmod(i, E)
            bi = int(b.page_block[p])
            out.add(bytes(b.blocks[bi].trace_ids[p - b.page_offset[bi], e]))
        return out

    def canon(self):
        """(count, sorted matched flat indices): comparable between
        stagings of the same blocks."""
        return (self.count,
                sorted(int(i) for s, i in zip(self.scores, self.idx)
                       if s >= 0))


def scan_batch(blocks, req, *, top_k=128, mesh=None, probe_min_vals=0,
               engine=None, batch=None, host_only=False,
               structural=None):
    """Stage `blocks` (ColumnarPages, one or many) as ONE batch on a
    MultiBlockEngine (over `mesh` if given), compile `req` against it
    and launch once: how a test scans where it does not need the
    batcher. `structural`: an ir expression to compile and attach.
    Pass `engine`/`batch` to reuse a staging. `host_only`: the host
    route instead (batcher.host_scan over the host-tier stack, compiled
    without the device)."""
    from tempo_tpu.search.batcher import host_scan
    from tempo_tpu.search.engine import resolve_top_k
    from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi

    blocks = list(blocks)
    if engine is None:
        engine = MultiBlockEngine(top_k=top_k, mesh=mesh,
                                  device_probe_min_vals=probe_min_vals)
    if batch is None:
        batch = (engine.stage_host(blocks) if host_only
                 else engine.stage(blocks))
    mq = compile_multi(blocks, req, cache_on=batch, host_only=host_only)
    if mq is None:
        return BatchScan(engine, batch, None)
    if structural is not None:
        from tempo_tpu.search.structural import compile_structural

        mq.structural = compile_structural(
            structural, blocks, cache_on=batch,
            staged_dicts=None if host_only else batch.staged_dicts,
            host_only=host_only,
            entry_kv_slots=blocks[0].geometry.kv_per_entry)
    if host_only:
        k = resolve_top_k(engine.top_k, mq.limit)
        return BatchScan(engine, batch, mq, host_scan(batch, mq, k)[:4])
    return BatchScan(engine, batch, mq, engine.scan(batch, mq)[:4])


def drop_hbm(batcher):
    """Every staged group leaves HBM, through the cache's own
    accounting, with no eviction booked; the host tier stays. (Both
    tiers: `batcher.cache.invalidate(set())`.)"""
    cache = batcher.cache
    for gkey in cache.snapshot()["entries"]:
        with cache.group_lock:
            cache._remove_locked(gkey)


def settle(batcher, timeout=10.0):
    """Look-aheads that no search came back for give their pins back
    when they finish: wait for that, then return the pins still held."""
    import time

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        snap = batcher.cache.snapshot()
        held = sum(pins for _n, pins, _m in snap["entries"].values())
        if not held and not snap["staging"]:
            return 0
        time.sleep(0.02)
    return held


def check_budget(cache):
    """The budget's one invariant, on a cache at rest: the running
    totals are the sums over the resident entries. Returns the entries'
    snapshot."""
    from tempo_tpu.search import group_cache

    snap = cache.snapshot()
    entries = snap["entries"]
    held = [cache.resident(k) for k in entries]
    assert snap["hbm_bytes"] == sum(n for n, _p, _m in entries.values()) >= 0
    with cache.group_lock:
        assert cache._probe_dict_total == sum(
            group_cache._dict_bytes(e.batch) for e in held)
        assert cache._span_total == sum(
            group_cache._span_bytes(e.batch) for e in held)
        assert cache._cache_logical == sum(e.logical for e in held)
        assert cache._agg_total == sum(e.agg_bytes for e in held)
    return entries


def staged_dict(pages, probe_min_vals=1):
    """The DeviceDict a one-block batch stages for the block's value
    dictionary (None under the threshold or a planner veto)."""
    from tempo_tpu.search.multiblock import stack_blocks

    dicts = stack_blocks([pages], probe_min_vals=probe_min_vals).staged_dicts
    return next(iter(dicts.values()), None)
