"""Multi-host wiring: jax.distributed initialization + helpers.

The TPU-native replacement for the reference's cross-process worker
fabric (querier worker pools dialing frontends over gRPC,
modules/querier/worker/worker.go:23-51): hosts join one JAX distributed
runtime, the device mesh spans every host's chips (ICI within a slice,
DCN across — SURVEY.md §2.6), and the scan engine's collectives do the
cross-host reduction that the reference does with response merging.

Config/env contract (cli/config.py `distributed:` section):

    distributed:
      coordinator: "10.0.0.1:8476"   # or ${TEMPO_COORDINATOR}
      num_processes: 8               # or ${TEMPO_NUM_PROCESSES}
      process_id: ${TEMPO_PROCESS_ID}
      cpu_devices_per_host: 0        # >0 = CPU dryrun (gloo collectives)

A v5e-64 deployment (BASELINE config 5) is 16 hosts × 4 chips:
num_processes=16, coordinator on host 0, one process per host; the
"shards" mesh axis then spans all 64 chips.
"""

from __future__ import annotations

import os


def init_distributed(coordinator: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None,
                     cpu_devices_per_host: int | str | None = 0) -> bool:
    """Join the JAX distributed runtime. Args fall back to
    TEMPO_COORDINATOR / TEMPO_NUM_PROCESSES / TEMPO_PROCESS_ID env vars.
    Returns True if distributed mode was initialized, False when no
    coordinator is configured (single-host mode — the common case).

    Must run before anything touches jax devices. With
    cpu_devices_per_host > 0 the process simulates that many chips on
    CPU with gloo collectives — the localhost dryrun path
    (__graft_entry__.dryrun_multihost)."""
    coordinator = coordinator or os.environ.get("TEMPO_COORDINATOR", "")
    if not coordinator:
        return False
    # YAML env substitution delivers strings — coerce
    if num_processes is None or num_processes == "":
        num_processes = int(os.environ.get("TEMPO_NUM_PROCESSES", "0")) or None
    else:
        num_processes = int(num_processes)
    if process_id is None or process_id == "":
        pid_env = os.environ.get("TEMPO_PROCESS_ID")
        process_id = int(pid_env) if pid_env is not None else None
    else:
        process_id = int(process_id)
    # empty env substitution / bare YAML key → disabled, like the others
    cpu_devices_per_host = int(cpu_devices_per_host or 0)

    import jax

    if cpu_devices_per_host:
        # jax.config, not env: jax read its env vars at import, and an
        # inherited XLA_FLAGS device count (the test harness's 8) must
        # not leak in — this process gets exactly its own
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", int(cpu_devices_per_host))
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def ownership_members() -> tuple[list[str], str]:
    """(fleet member ids, this process's id) for the HBM ownership map
    (search/ownership.py), derived from the distributed env contract
    WITHOUT importing jax — a write-only process must not initialize a
    device backend just to learn the fleet shape. Single-host (no
    TEMPO_NUM_PROCESSES) is a one-member fleet that owns everything.
    Every process derives the identical ordered list, so the placement
    tables agree fleet-wide with zero coordination."""
    n = int(os.environ.get("TEMPO_NUM_PROCESSES", "0") or 0)
    pid = int(os.environ.get("TEMPO_PROCESS_ID", "0") or 0)
    if n > 1:
        return [f"host-{i}" for i in range(n)], f"host-{pid}"
    return ["self"], "self"


def is_multiprocess() -> bool:
    import jax

    return jax.process_count() > 1


def process_index() -> int:
    import jax

    return jax.process_index()
