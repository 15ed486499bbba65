"""Corpus generator `otel_blocks`: search blocks of a read-side tenant.

`generate(params, seed, backend_dir, pool) -> manifest`

Entries, dictionaries and times are functions of `seed`; block ids are a
function of the configuration's name and the block's index only (see
`block_id`). The generator keeps its own arrays (one value id per entry
and attribute key, starts, durations, per-block dictionary membership)
in the manifest: they are what `chipbench/reference.py` scans. Only
after that are they packed into the program's container format
(`ColumnarPages.to_bytes`, `compress`, `BlockMeta`, `LocalBackend`): the
format is the program's, the answers are not.

Vocabulary (OpenTelemetry HTTP/RPC/DB/k8s conventions). Values are
built so that a queried needle is a substring only of the values it is
meant to hit: numeric needles (status codes) occur in no other value,
a full service name is a prefix only of that service's pods, and a
role infix (`-gateway`) is shared by one service per team.
"""

from __future__ import annotations

import hashlib
import json
import uuid
import zlib

import numpy as np

PAGE_ENTRIES = 1024
TRACE_ID_TAG = b"chipbnch"

# attribute keys in sorted order (ColumnarPages.build fills an entry's
# slots in sorted-key order); the second field is the share of entries
# that carry the key
KEYS = (
    ("cloud.region", 1.0),
    ("customer.id", 0.8),
    ("db.system", 0.3),
    ("deployment.environment", 1.0),
    ("http.method", 1.0),
    ("http.route", 1.0),
    ("http.status_code", 1.0),
    ("k8s.namespace.name", 1.0),
    ("k8s.pod.name", 0.9),
    ("messaging.system", 0.15),
    ("name", 1.0),
    ("rpc.method", 0.45),
    ("rpc.service", 0.45),
    ("service.name", 1.0),
    ("telemetry.sdk.language", 0.95),
    ("user.tier", 0.5),
)
KEY_NAMES = tuple(k for k, _ in KEYS)

TEAMS = ("ads", "billing", "catalog", "checkout", "identity", "logistics",
         "payments", "search", "support", "warehouse")
ROLES = ("api", "auth", "batch", "cache", "cron", "edge", "gateway",
         "indexer", "ledger", "mailer", "notifier", "planner", "proxy",
         "queue", "router", "scheduler", "store", "sync", "web", "worker")
STATUS = (("200", 0.94), ("201", 0.012), ("204", 0.008), ("301", 0.002),
          ("400", 0.008), ("401", 0.004), ("403", 0.003), ("404", 0.008),
          ("429", 0.002), ("500", 0.01), ("502", 0.001), ("503", 0.002))
REGIONS = (("us-east-1", 0.4), ("us-west-2", 0.25), ("eu-west-1", 0.25),
           ("ap-south-1", 0.1))
ENVS = (("production", 0.9), ("staging", 0.08), ("canary", 0.02))
HTTP_METHODS = (("GET", 0.6), ("POST", 0.25), ("PUT", 0.06),
                ("DELETE", 0.04), ("PATCH", 0.03), ("HEAD", 0.02))
DB_SYSTEMS = ("postgresql", "mysql", "redis", "cassandra", "dynamodb",
              "elasticsearch", "mongodb", "spanner")
MSG_SYSTEMS = ("kafka", "pubsub", "rabbitmq", "sqs")
SDKS = (("go", 0.4), ("java", 0.3), ("python", 0.15), ("nodejs", 0.1),
        ("rust", 0.05))
TIERS = (("free", 0.7), ("pro", 0.25), ("enterprise", 0.05))


def _letters(i: int, n: int) -> str:
    """`i` as `n` lowercase letters, so no value grows a digit run."""
    out = []
    for _ in range(n):
        i, r = divmod(i, 26)
        out.append(chr(97 + r))
    return "".join(reversed(out))


def _zipf(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return w / w.sum()


def _weights(pairs) -> np.ndarray:
    w = np.array([p for _, p in pairs], dtype=np.float64)
    return w / w.sum()


def vocabulary(params: dict) -> dict:
    """The value domains, independent of the seed (the seed decides which
    entry carries which value). `domains[key] = (values, probabilities)`;
    pods are per service (`pods_per_service` each)."""
    s = float(params.get("zipf_s", 1.1))
    n_services = len(TEAMS) * len(ROLES)
    if params.get("services", n_services) != n_services:
        raise ValueError(f"services is {n_services} by construction")
    services = [f"{t}-{r}" for t in TEAMS for r in ROLES]
    n_pods = int(params["pods"])
    per = max(1, n_pods // n_services)
    pods = [f"{svc}-{_letters(j * 7919 + i, 5)}"
            for i, svc in enumerate(services) for j in range(per)]
    routes = [f"/api/{_letters(i // 10 + 300, 3)}/{_letters(i, 4)}"
              for i in range(int(params["routes"]))]
    methods = [f"{_letters(i // 5 + 900, 3).capitalize()}Service/"
               f"{_letters(i, 4).capitalize()}"
               for i in range(int(params["rpc_methods"]))]
    rpc_services = [f"{_letters(i + 900, 3).capitalize()}Service"
                    for i in range(max(1, int(params["rpc_methods"]) // 5))]
    customers = [f"cus_{_letters(i * 48271 % 456976, 4)}{_letters(i, 3)}"
                 for i in range(int(params["customers"]))]
    names = [f"op_{_letters(i, 3)}" for i in range(int(params["span_names"]))]
    z = lambda vals: (vals, _zipf(len(vals), s))  # noqa: E731
    flat = lambda pairs: ([v for v, _ in pairs], _weights(pairs))  # noqa: E731
    domains = {
        "cloud.region": flat(REGIONS),
        "customer.id": z(customers),
        "db.system": (list(DB_SYSTEMS), _zipf(len(DB_SYSTEMS), s)),
        "deployment.environment": flat(ENVS),
        "http.method": flat(HTTP_METHODS),
        "http.route": z(routes),
        "http.status_code": flat(STATUS),
        "k8s.namespace.name": (list(TEAMS), None),   # follows the service
        "k8s.pod.name": (pods, None),                # follows the service
        "messaging.system": (list(MSG_SYSTEMS), _zipf(len(MSG_SYSTEMS), s)),
        "name": z(names),
        "rpc.method": z(methods),
        "rpc.service": z(rpc_services),
        "service.name": z(services),
        "telemetry.sdk.language": flat(SDKS),
        "user.tier": flat(TIERS),
    }
    table = sorted({v for vals, _ in domains.values() for v in vals})
    if len(table) > 32_767:
        raise ValueError("dictionary would not fit int16 value ids")
    return {"domains": domains, "table": table, "pods_per_service": per,
            "services": services, "teams": TEAMS, "roles": ROLES}


def block_id(config_name: str, index: int, n_pages: int,
             max_batch_pages: int = 4096) -> str:
    """A block's id from the configuration's name and the block's index,
    never from the seed: the program's batcher cuts its groups by a hash
    of the ids and the blocks-per-group count is a jit shape, so ids from
    the seed would make every new seed compile a new set of kernels.

    The id also steps past the batcher's content-defined cut anchors
    (`search/batcher.py _cuts`: crc32 of the job key modulo
    max_batch_pages / 2 / n_pages; the rule is restated here, nothing is
    imported), so that groups fill to the page cap. This is the one
    place where the corpus follows the program instead of a deployment,
    whose ids are random uuids: those give 14 groups of 10 distinct
    sizes for this tenant, each size a set of 9 to 17 kernel shapes of
    some 8 s of cold XLA compile apiece, which no first run of a cell
    fits into its 1,200 s (PERF.md section 4 has the reckoning, and what
    it hides). Every run prints how many groups the program staged: a
    change to the program's rule shows there, in the jit keys and in
    `setup_s`. When the program's kernel shapes no longer depend on the
    group's block count (ROADMAP S3), a benchmark PR drops the loop."""
    divisor = max(2, max_batch_pages // (2 * max(1, n_pages)))
    salt = 0
    while True:
        bid = str(uuid.UUID(hashlib.md5(
            f"chipbench/{config_name}/{index}/{salt}".encode()).hexdigest()))
        if zlib.crc32(repr((bid, 0, n_pages)).encode()) % divisor:
            return bid
        salt += 1


def trace_ids(block: int, n_pages: int) -> np.ndarray:
    """uint8 [P, E, 16]: big-endian block index, big-endian flat entry
    index, a fixed tag. Unique across the tenant and invertible, so an
    answer's id names its entry (see `entry_of_trace_id`)."""
    ids = np.zeros((n_pages, PAGE_ENTRIES, 16), dtype=np.uint8)
    ids[:, :, 0:4] = np.frombuffer(
        np.array([block], dtype=">u4").tobytes(), dtype=np.uint8)
    flat = np.arange(n_pages * PAGE_ENTRIES, dtype=">u4")
    ids[:, :, 4:8] = flat.view(np.uint8).reshape(n_pages, PAGE_ENTRIES, 4)
    ids[:, :, 8:16] = np.frombuffer(TRACE_ID_TAG, dtype=np.uint8)
    return ids


def entry_of_trace_id(hex_id: str):
    raw = bytes.fromhex(hex_id.rjust(32, "0"))
    if raw[8:16] != TRACE_ID_TAG:
        return None
    return int.from_bytes(raw[0:4], "big"), int.from_bytes(raw[4:8], "big")


def duration_ms_quantile(params: dict, q: float) -> int:
    """Quantile of the log-normal duration law, by bisection on erf."""
    import math
    lo, hi = -8.0, 8.0
    for _ in range(80):
        mid = (lo + hi) / 2
        if 0.5 * (1 + math.erf(mid / math.sqrt(2))) < q:
            lo = mid
        else:
            hi = mid
    return int(math.exp(math.log(params["dur_median_ms"])
                        + params["dur_sigma"] * lo))


def _draw(rng, p: np.ndarray, n: int) -> np.ndarray:
    return np.searchsorted(np.cumsum(p), rng.random(n), side="right").clip(
        0, len(p) - 1).astype(np.int32)


def make_block(params: dict, vocab: dict, gid: dict, seed: int, index: int):
    """One block's arrays from (seed, index): `vals` int16 [N, K] global
    value id per entry and key (-1 = the entry lacks the key), `start`,
    `end` uint32 unix seconds, `dur` uint32 ms."""
    n = int(params["entries_per_block"])
    rng = np.random.default_rng([seed % (1 << 32), seed >> 32, index])
    dom = vocab["domains"]
    vals = np.full((n, len(KEYS)), -1, dtype=np.int16)
    svc = _draw(rng, dom["service.name"][1], n)
    per = vocab["pods_per_service"]
    for c, (key, share) in enumerate(KEYS):
        if key == "service.name":
            local = svc
        elif key == "k8s.namespace.name":
            local = svc // len(ROLES)
        elif key == "k8s.pod.name":
            local = svc * per + _draw(rng, _zipf(per, 1.1), n)
        else:
            local = _draw(rng, dom[key][1], n)
        col = gid[key][local]
        if share < 1.0:
            col = np.where(rng.random(n) < share, col, -1)
        vals[:, c] = col
    window = float(params["time_span_s"]) / int(params["blocks"])
    lo = params["time_base"] + index * window
    start = (lo + rng.random(n) * window * (1 + params["time_overlap"])
             ).astype(np.uint32)
    dur = np.exp(np.log(params["dur_median_ms"])
                 + params["dur_sigma"] * rng.standard_normal(n))
    dur = np.clip(dur, 1, 3_600_000).astype(np.uint32)
    end = start + (dur + 999) // 1000
    return vals, start, end.astype(np.uint32), dur


def pack_block(vals, start, end, dur, table, block: int):
    """The generator's arrays in the program's container: slots filled in
    sorted-key order, per-block sorted dictionaries of the values the
    block holds, header rollups."""
    from tempo_tpu.search.columnar import ColumnarPages, PageGeometry

    n, K = vals.shape
    P = -(-n // PAGE_ENTRIES)
    have = vals >= 0
    C = 1
    while C < int(have.sum(axis=1).max()):
        C *= 2
    present = np.zeros(len(table), dtype=bool)
    present[vals[have]] = True
    remap = (np.cumsum(present) - 1).astype(np.int32)
    key_present = have.any(axis=0)
    key_remap = (np.cumsum(key_present) - 1).astype(np.int32)
    slot = np.cumsum(have, axis=1) - 1
    rows, cols = np.nonzero(have)
    kv_key = np.full((P * PAGE_ENTRIES, C), -1, dtype=np.int32)
    kv_val = np.full((P * PAGE_ENTRIES, C), -1, dtype=np.int32)
    kv_key[rows, slot[rows, cols]] = key_remap[cols]
    kv_val[rows, slot[rows, cols]] = remap[vals[rows, cols]]
    valid = np.zeros(P * PAGE_ENTRIES, dtype=bool)
    valid[:n] = True

    def paged(a, dtype):
        out = np.zeros(P * PAGE_ENTRIES, dtype=dtype)
        out[:n] = a
        return out.reshape(P, PAGE_ENTRIES)

    svc_c, name_c = KEY_NAMES.index("service.name"), KEY_NAMES.index("name")
    header = {
        "n_entries": n, "n_pages": P, "entries_per_page": PAGE_ENTRIES,
        "kv_per_entry": C, "n_keys": int(key_present.sum()),
        "n_vals": int(present.sum()), "truncated_entries": 0,
        "min_start_s": int(start.min()), "max_end_s": int(end.max()),
        "min_dur_ms": int(dur.min()), "max_dur_ms": int(dur.max()),
    }
    pages = ColumnarPages(
        geometry=PageGeometry(PAGE_ENTRIES, C),
        key_dict=[k for k, p in zip(KEY_NAMES, key_present) if p],
        val_dict=[table[i] for i in np.flatnonzero(present)],
        kv_key=kv_key.reshape(P, PAGE_ENTRIES, C),
        kv_val=kv_val.reshape(P, PAGE_ENTRIES, C),
        entry_start=paged(start, np.uint32), entry_end=paged(end, np.uint32),
        entry_dur=paged(dur, np.uint32),
        entry_valid=valid.reshape(P, PAGE_ENTRIES),
        entry_root_svc=paged(remap[vals[:, svc_c]], np.int32),
        entry_root_name=paged(remap[vals[:, name_c]], np.int32),
        trace_ids=trace_ids(block, P), n_entries=n, header=header)
    return pages, present, key_present


def generate(params: dict, seed: int, backend_dir: str, pool) -> dict:
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.backend.types import (
        BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER,
    )
    from tempo_tpu.encoding.v2.compression import compress

    vocab = vocabulary(params)
    table = vocab["table"]
    index_of = {v: i for i, v in enumerate(table)}
    gid = {k: np.array([index_of[v] for v in vals], dtype=np.int16)
           for k, (vals, _) in vocab["domains"].items()}
    n_blocks, n = int(params["blocks"]), int(params["entries_per_block"])
    n_pages = -(-n // PAGE_ENTRIES)
    tenant = params["tenant"]
    be = LocalBackend(backend_dir)
    K = len(KEYS)
    vals_all = np.empty((n_blocks, K, n), dtype=np.int16)  # key-major
    start_all = np.empty((n_blocks, n), dtype=np.uint32)
    end_all = np.empty((n_blocks, n), dtype=np.uint32)
    dur_all = np.empty((n_blocks, n), dtype=np.uint32)
    present_all = np.zeros((n_blocks, len(table)), dtype=bool)
    key_present_all = np.zeros((n_blocks, K), dtype=bool)
    ids = [block_id(params["config_name"], i, n_pages)
           for i in range(n_blocks)]

    def one(i: int) -> tuple:
        vals, start, end, dur = make_block(params, vocab, gid, seed, i)
        vals_all[i], start_all[i], end_all[i], dur_all[i] = (
            vals.T, start, end, dur)
        pages, present_all[i], key_present_all[i] = pack_block(
            vals, start, end, dur, table, i)
        blob = compress(pages.to_bytes(), "zstd")
        hdr = dict(pages.header)
        hdr["encoding"] = "zstd"
        hdr["compressed_size"] = len(blob)
        m = BlockMeta(tenant_id=tenant, encoding="zstd", block_id=ids[i],
                      start_time=hdr["min_start_s"],
                      end_time=hdr["max_end_s"])
        m.search_pages = hdr["n_pages"]
        m.search_size = len(blob)
        m.search_entries_per_page = hdr["entries_per_page"]
        m.search_kv_per_entry = hdr["kv_per_entry"]
        m.total_objects = hdr["n_entries"]
        be.write(tenant, m.block_id, NAME_SEARCH, blob)
        be.write(tenant, m.block_id, NAME_SEARCH_HEADER,
                 json.dumps(hdr).encode())
        be.write_block_meta(m)
        return len(blob), hdr["kv_per_entry"]

    written = list(pool.map(one, range(n_blocks)))
    return {
        "tenant": tenant, "blocks": {tenant: n_blocks}, "block_ids": ids,
        "entries": n_blocks * n, "pages": n_blocks * n_pages,
        "kv_per_entry": max(c for _, c in written),
        "disk_bytes": sum(n for n, _ in written), "table": table,
        "key_names": KEY_NAMES,
        "vals": vals_all, "start": start_all, "end": end_all,
        "dur": dur_all, "present": present_all,
        "key_present": key_present_all,
        "vocab": {"services": vocab["services"], "teams": list(TEAMS),
                  "roles": list(ROLES),
                  "domains": {k: (vals, None if p is None else p.tolist())
                              for k, (vals, p) in vocab["domains"].items()}},
        "dur_ms_quantile": lambda q: duration_ms_quantile(params, float(q)),
        "time_base": params["time_base"],
        "time_span_s": params["time_span_s"],
        "entry_of_trace_id": entry_of_trace_id,
    }
