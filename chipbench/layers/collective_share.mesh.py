"""Kernel, on a mesh: what the merge across shards costs: device time of
the collective operations in the traced window over the devices' summed
busy time (`trace["ops_ns"]` and `trace["devices"]` both cover every
device plane). A collective's time on a device includes its wait for
the slowest shard. Collectives are told by the HLO op's name: every op
whose name starts with one of PREFIXES, so the async pairs
(`all-reduce-start` / `all-reduce-done`, `all-gather-start` / `-done`)
and numbered copies (`all-reduce.3`) count. The first traced run on the
chips (PR 26, four v5e, `jit_dist_multi_scan_kernel` and
`jit_dist_coalesced_scan_kernel`) had the synchronous forms only, no
`-start`/`-done` pairs: NAMES_SEEN. The two `psum`s of a launch are one
`all-reduce`, its two `all_gather`s two `all-gather`s."""

PREFIXES = ("all-reduce", "all-gather", "collective-permute", "all-to-all",
            "reduce-scatter")
# the op names of the first traced run on the chips, for the next reader:
# the solo kernel's three, then the fused kernel's two gathers
NAMES_SEEN = ("all-reduce.1", "all-gather.2", "all-gather.3",
              "all-gather.12", "all-gather.13")


def is_collective(op: str) -> bool:
    return op.startswith(PREFIXES)


def compute(run):
    trace = run.get("trace")
    if not trace or len(trace.get("devices", ())) < 2:
        return None
    busy = sum(d["busy_ns"] for d in trace["devices"])
    if not busy:
        return None
    ns = sum(v for k, v in trace["ops_ns"] if is_collective(k))
    return 100.0 * ns / busy
