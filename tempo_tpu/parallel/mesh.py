"""Device mesh construction.

The TPU reinterpretation of the reference's data-distribution strategies
(SURVEY.md §2.5): block/page shards map onto mesh axes the way search jobs
map onto queriers. One axis — "shards" — carries the scan fan-out
(pages × blocks are data-parallel); collectives ride ICI within a slice
and DCN across slices, replacing the goroutine fan-out + Results channel.
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

SCAN_AXIS = "shards"

# Collective-program dispatch order must be IDENTICAL on every device:
# two threads enqueueing shard_map programs concurrently can interleave
# the per-device queues (dev0 runs A then B, dev1 runs B then A) and the
# collectives rendezvous-deadlock — observed as a multi-minute zero-CPU
# hang. ONE process-wide lock covers every dispatch site (scan kernels,
# the dictionary probe, any future collective): per-engine locks are not
# enough, because the probe dispatches during query compilation while a
# different engine thread may be mid-scan on the same devices.
dispatch_lock = threading.Lock()


@contextlib.contextmanager
def locked_collective(rec=None):
    """Hold the process-wide collective dispatch lock, attributing the
    time spent QUEUED behind other dispatches to the profiler record's
    `lock_wait` stage (rec = observability.profile dispatch record or
    None). Under concurrent mesh searches this wait is serialization the
    operator can't otherwise see — it looks like kernel time.

    The wait is BOUNDED (`search_dispatch_lock_timeout_s`, via
    robustness.GUARD.lock_timeout_s): a dispatch wedged while holding
    this lock used to block every later submitter forever (the PR 1
    rendezvous-deadlock class). A timed-out wait now books a device
    fault into the circuit breaker and raises DispatchLockTimeout, so
    the submitter falls back to the host path instead of stacking.
    <= 0 restores the unbounded wait."""
    from tempo_tpu.robustness import BREAKER, GUARD, FAULTS
    from tempo_tpu.robustness.dispatch import DispatchLockTimeout
    from tempo_tpu.observability import metrics as obs
    from tempo_tpu.observability import tracing

    timeout = GUARD.lock_timeout_s
    t0 = tracing.now_ns()
    # the wait as a `dispatch.lock_wait` span: its thread's CPU clock
    # beside the stamps, only where the record keeps intervals
    c0 = (tracing.cpu_ns()
          if rec is not None and rec.intervals is not None else None)
    if timeout and timeout > 0:
        ok = dispatch_lock.acquire(timeout=timeout)
    else:
        ok = dispatch_lock.acquire()
    if not ok:
        obs.dispatch_lock_timeouts.inc()
        msg = (f"collective dispatch lock not acquired within "
               f"{timeout:.1f}s — another dispatch is wedged while "
               "holding it")
        BREAKER.record_fault("lock_timeout", mode="mesh", detail=msg)
        raise DispatchLockTimeout(msg)
    try:
        if rec is not None:
            rec.add_interval(
                "lock_wait", t0, tracing.now_ns(), c0,
                tracing.cpu_ns() if rec.intervals is not None else None)
        if FAULTS.active:
            # simulates a dispatch wedged INSIDE the collective section
            # (holding the lock): later submitters hit the bounded wait
            FAULTS.hit("dispatch_lock_hang")
        yield
    finally:
        dispatch_lock.release()


def scan_mesh_axes() -> tuple[str, ...]:
    return (SCAN_AXIS,)


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    import numpy as np

    return Mesh(np.array(devs), (SCAN_AXIS,))


@functools.lru_cache(maxsize=None)
def replicated(mesh: Mesh) -> NamedSharding:
    """The placement of a collective launch's replicated operands (the
    `P()` entries of its shard_map's in_specs): whole on every device
    of the mesh."""
    return NamedSharding(mesh, PartitionSpec())


def placed_for(arr, mesh) -> bool:
    """Whether a device array lives where a launch over `mesh` (None:
    one device, no mesh) reads it without moving it."""
    on = getattr(arr.sharding, "mesh", None)
    return on is None if mesh is None else on == mesh


def put(tree, sharding):
    """Host or device arrays (any pytree; None leaves stay None) placed
    on the mesh with `sharding`, committed, in one `jax.device_put`. An
    array that is already there comes back as it is."""
    if jax.process_count() > 1:
        # multi-host: every process holds the same host value and
        # transfers only its own devices' slices (a device_put of a
        # global array would need every device to be addressable)
        import numpy as np

        def place(v):
            if isinstance(v, jax.Array) and v.sharding == sharding:
                return v
            v = np.asarray(v)
            return jax.make_array_from_callback(
                v.shape, sharding, lambda idx: v[idx])

        return jax.tree_util.tree_map(place, tree)
    return jax.device_put(tree, sharding)


def put_replicated(mesh: Mesh, tree):
    """The ONE way a collective launch's replicated operands get to the
    mesh, done before `locked_collective` is entered so that the locked
    section is the enqueue alone. An operand left uncommitted on one
    device is instead re-placed on every device inside the jit call, on
    its slow path, under the lock, at every launch."""
    return put(tree, replicated(mesh))


def shard_map_compat(f, mesh, in_specs, out_specs, check: bool = False):
    """`jax.shard_map` with this repo's defaults (replication checking
    off unless asked); every distributed kernel routes through here."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
