"""Corpus generator `otel_calltree`: `otel_blocks`' search blocks with a
span tree behind every trace, as a tenant's ingesters write them with
`search_structural_enabled` (the span segment of the search container:
`ColumnarPages.span_*`).

`generate(params, seed, backend_dir, pool) -> manifest`

Everything at trace level is `otel_blocks`': keys, their shares, the
value domains and laws, durations, times (`make_block`), block ids
(`block_id`) and trace ids (`trace_ids`). What this file adds is the
spans of each trace, and a container that holds them (`pack_block`: the
dictionaries are the union of what entries and spans hold).

**The shape of a trace.** DeathStarBench's `socialNetwork` (Gan et al.,
ASPLOS 2019), whose `wrk2` script `mixed-workload.lua` sends 60 %
read-home-timeline, 30 % read-user-timeline and 10 % compose-post: three
fixed call trees, nginx front end -> logic services -> each service's
own cache and store (TEMPLATES below; written from the application's
published architecture, there is no network here to read a trace from).
Each of the tenant's 10 teams runs one copy of the application over its
20 roles (ROLE_OF), so a span's `service.name` is a value the trace-level
dictionary already has and a (parent service, child service) pair is an
edge of one fixed graph (`call_edges` in the manifest). A trace's team is
that of its entry's `service.name`; every team's cache is `redis`, its
document store the flavour that team runs (STORE_OF_TEAM).

**The tail.** Those trees are narrow and all alike. Luo et al. (SoCC
2021, Alibaba `cluster-trace-microservices-v2021`) found call-graph sizes
heavy-tailed, depth small for nearly all, the width from repeated calls
to stateless services and caches. So in `tail_share` of the read traces
the post store's fan-out (a read of k posts: one call to its cache and
one to its store for each) repeats k times, k Pareto(`tail_alpha`) from
`tail_kmin`, and in `chain_share` of those a chain of nested calls hangs
under the store (depth: 3 + geometric(`chain_p`), at most `chain_max`).

**The order of a trace's spans** is the one an ingester stores
(`tempo_tpu/search/data.py collect_span_rows`: the payload's, one
resource batch after another, parents resolved by span id afterwards).
A service's SDK exports its own spans, so a trace's spans lie grouped by
service, one batch a service (a regrouped trace); the batches in the
order they reached the ingester, which each service's export timer
decides and not the tree: a random order a trace; within a batch in the
order the spans ENDED, which is when an SDK's batch processor queues
them: calls are sequential and a child ends inside its parent, so a
service's spans leave in post-order, children before their parent. So a
span's parent lies before it or behind it, and a subtree is no run of
the span axis. A trace keeps its first `max_spans` (512) spans in that
order, which is what the shipped `search_structural_max_spans` keeps at
ingest; a kept span whose parent was cut has no parent (-1), as
`collect_span_rows` leaves it. `cut_traces` counts the traces that lost
spans, `orphan_spans` the kept spans that lost their parent.

**A span** carries 2-4 kvs in 4 slots, in sorted-key order: always
`service.name` and `name`; a front end's server span and every rpc
client `http.status_code`; rpc clients and servers `rpc.method`; a call
to a cache or store `db.system`; producer and consumer
`messaging.system`. Kind by its place in the tree (server 2, client 3,
producer 4, consumer 5, internal 1). The root's duration is the entry's;
a child's is a drawn share (0.05-0.95) of its parent's, rounded down, so
shorter wherever the parent is not 0. An entry whose `http.status_code`
is 500 is an error trace: the error is born on one span drawn from the
trace and `http.status_code=500` is carried up its path to the root
(every other status is the root's alone).

**The span count is nearly a constant of the block.** A group's span
axis is padded to a power of two and is a jit shape, so a block's total
must not wander: templates, optional spans, tail and chain traces are
dealt in fixed numbers per block (a shuffled multiset, not a draw per
trace) and the tail's sizes are drawn one in each of as many equal
shares of the law's mass as there are tail traces. What is left to vary
is which trace got what: a block's total moves by a few spans in 770,000.

The manifest keeps, for the plain reference
(`chipbench/reference_structural.py`), per block: `span_count` [N],
`span_parent` (index within the block, -1 root), `span_dur`,
`span_kind`, `span_vals` int16 [S, 6] (global value id by SPAN_KEYS, -1 =
the span lacks the key); and `otel_blocks`' trace-level arrays.

Generator and op exit 1 at once on a program whose `/metrics` has no
`tempo_search_structural_span_rows_total` (`require_span_counters`): the
one such program, PR 44's parent, cannot end this cell inside the time a
run may take. PARENT_RUN says what it did when it was driven; a
benchmark that tries a new cell on the parent first needs a result or a
refusal from it, and a run cut at its limit is neither. Once a parent
has the counter this never fires: delete it then (PERF.md section 7).
"""

from __future__ import annotations

import json

import numpy as np

from chipbench.generators import otel_blocks as ob

# span-level keys, in sorted order (a span's slots fill in that order)
SPAN_KEYS = ("db.system", "http.status_code", "messaging.system", "name",
             "rpc.method", "service.name")
K_DB, K_STATUS, K_MSG, K_NAME, K_RPC, K_SVC = range(6)
SPAN_SLOTS = 4
INTERNAL, SERVER, CLIENT, PRODUCER, CONSUMER = 1, 2, 3, 4, 5

# socialNetwork's services on the tenant's roles
ROLE_OF = {
    "nginx": "gateway", "compose-post": "api", "text": "planner",
    "user-mention": "notifier", "url-shorten": "proxy", "media": "batch",
    "unique-id": "sync", "user": "auth", "post-storage": "store",
    "user-timeline": "router", "home-timeline": "web",
    "social-graph": "indexer", "write-home-timeline-mq": "queue",
    "write-home-timeline": "worker",
}
CACHE = "redis"
STORE_OF_TEAM = ("mongodb", "postgresql", "mysql", "cassandra", "mongodb",
                 "dynamodb", "elasticsearch", "spanner", "mongodb",
                 "postgresql")
MSG = "rabbitmq"

# one node: (parent node or -1, service, kind, operation, extra) where
# extra is "http" (front end's server span), "rpc" (a call or handler by
# rpc.method), "cache" / "store" (db.system), "msg"; the last field is
# the share of the template's traces that have the node (1.0 = all)
READ_HOME = (
    (-1, "nginx", SERVER, "/home-timeline/read", "http", 1.0),
    (0, "nginx", CLIENT, "ReadHomeTimeline", "rpc", 1.0),
    (1, "home-timeline", SERVER, "ReadHomeTimeline", "rpc", 1.0),
    (2, "home-timeline", CLIENT, "ZRevRange", "cache", 1.0),
    (2, "post-storage", SERVER, "ReadPosts", "rpc", 1.0),
    (4, "post-storage", CLIENT, "MGet", "cache", 1.0),
    (4, "post-storage", CLIENT, "Find", "store", 0.3),
)
READ_USER = (
    (-1, "nginx", SERVER, "/user-timeline/read", "http", 1.0),
    (0, "nginx", CLIENT, "ReadUserTimeline", "rpc", 1.0),
    (1, "user-timeline", SERVER, "ReadUserTimeline", "rpc", 1.0),
    (2, "user-timeline", CLIENT, "ZRevRange", "cache", 1.0),
    (2, "user-timeline", CLIENT, "Find", "store", 0.5),
    (2, "post-storage", SERVER, "ReadPosts", "rpc", 1.0),
    (5, "post-storage", CLIENT, "MGet", "cache", 1.0),
    (5, "post-storage", CLIENT, "Find", "store", 0.3),
)
COMPOSE = (
    (-1, "nginx", SERVER, "/post/compose", "http", 1.0),
    (0, "nginx", CLIENT, "ComposePost", "rpc", 1.0),
    (1, "compose-post", SERVER, "ComposePost", "rpc", 1.0),
    (2, "text", SERVER, "ComposeText", "rpc", 1.0),
    (3, "url-shorten", SERVER, "ComposeUrls", "rpc", 1.0),
    (4, "url-shorten", CLIENT, "Insert", "store", 1.0),
    (3, "user-mention", SERVER, "ComposeUserMentions", "rpc", 1.0),
    (6, "user-mention", CLIENT, "MGet", "cache", 1.0),
    (6, "user-mention", CLIENT, "Find", "store", 0.5),
    (2, "unique-id", SERVER, "ComposeUniqueId", "rpc", 1.0),
    (2, "media", SERVER, "ComposeMedia", "rpc", 1.0),
    (2, "user", SERVER, "ComposeCreatorWithUserId", "rpc", 1.0),
    (11, "user", CLIENT, "Get", "cache", 1.0),
    (11, "user", CLIENT, "Find", "store", 0.5),
    (2, "post-storage", SERVER, "StorePost", "rpc", 1.0),
    (14, "post-storage", CLIENT, "Insert", "store", 1.0),
    (2, "user-timeline", SERVER, "WriteUserTimeline", "rpc", 1.0),
    (16, "user-timeline", CLIENT, "Update", "store", 1.0),
    (16, "user-timeline", CLIENT, "ZAdd", "cache", 1.0),
    (2, "write-home-timeline-mq", PRODUCER, "Publish", "msg", 1.0),
    (19, "write-home-timeline", CONSUMER, "WriteHomeTimeline", "msg", 1.0),
    (20, "social-graph", SERVER, "GetFollowers", "rpc", 1.0),
    (21, "social-graph", CLIENT, "Get", "cache", 1.0),
    (21, "social-graph", CLIENT, "Find", "store", 0.5),
    (20, "write-home-timeline", CLIENT, "ZAdd", "cache", 1.0),
    (20, "write-home-timeline", INTERNAL, "FanOut", None, 1.0),
    (2, "compose-post", INTERNAL, "UploadAll", None, 1.0),
)
TEMPLATES = (("read-home-timeline", READ_HOME, 4),   # node the tail hangs on
             ("read-user-timeline", READ_USER, 5),
             ("compose-post", COMPOSE, None))
# what the tail repeats under the post store's ReadPosts, k times
TAIL_PAIR = (("post-storage", CLIENT, "MGet", "cache"),
             ("post-storage", CLIENT, "Find", "store"))
CHAIN = ("post-storage", SERVER, "ReadPost", "rpc")
ROLES_WITH_SPANS = tuple(ROLE_OF)


SPAN_ROWS = "tempo_search_structural_span_rows_total"
# what PR 44's parent did under this cell's traffic on a v5e, 8 blocks,
# these files laid over it (my chip run, PR 44; PERF.md section 6; the
# corpus then had a trace in walk order, which costs a lookup no less)
PARENT_RUN = (
    "a `desc` launch took 5.5-6.9 s (26 -> 23 trips over 8.4M span rows, "
    "two lookups a trip), a `child` launch 0.71 s, the others 0.27-0.32 s; "
    "set-up's bursts (168 requests) took 233 s of a setup_s of 345 s; a "
    "burst of eight `desc` searches queued 55 s on the device, the 30 s "
    "dispatch watchdog booked 9 device faults, the breaker opened and 141 "
    "answers came from the host route: every answer equal to the reference, "
    "`correct: false`, 117 searches in a 51.8 s window, the whole run "
    "~420 s where a warm run may take 360")


def require_span_counters(who: str) -> None:
    """Exit, in `who`'s name, on a program that does not count the span
    rows it stages (the head of this file says why). It asks `/metrics`,
    the surface the cell's readers read, for that one name."""
    from tempo_tpu.observability.metrics import REGISTRY

    if f"# TYPE {SPAN_ROWS} " not in REGISTRY.expose():
        raise SystemExit(
            f"{who}: this program's /metrics has no {SPAN_ROWS}: a program "
            "from before PR 44, whose joins by ancestor run 23-26 trips "
            "where 9 reach every ancestor; not run (chipbench/generators/"
            "otel_calltree.py says what happened when one was)")


def call_edges() -> list:
    """(parent service, child service) of every template edge that
    crosses services, as socialNetwork names them, once each."""
    out = []
    for _, nodes, _ in TEMPLATES:
        for par, svc, *_ in nodes:
            if par >= 0 and nodes[par][1] != svc and \
                    (nodes[par][1], svc) not in out:
                out.append((nodes[par][1], svc))
    return out


def _exact(n: int, share: float, rng) -> np.ndarray:
    """bool [n] with round(share x n) true, placed by `rng`."""
    out = np.zeros(n, dtype=bool)
    out[rng.permutation(n)[:int(round(share * n))]] = True
    return out


class _Ids:
    """Global value ids of what spans carry, by team where it depends on
    the team."""

    def __init__(self, vocab: dict, index_of: dict):
        dom = vocab["domains"]
        self.svc = {s: np.array(
            [index_of[f"{t}-{r}"] for t in ob.TEAMS], dtype=np.int32)
            for s, r in ROLE_OF.items()}
        assert set(ROLE_OF.values()) <= set(ob.ROLES)
        names, methods = dom["name"][0], dom["rpc.method"][0]
        ops = sorted({(n[1], n[3]) for _, nodes, _ in TEMPLATES
                      for n in nodes} | {(t[0], t[2]) for t in TAIL_PAIR}
                     | {(CHAIN[0], CHAIN[2])})
        # an operation's span name and rpc method: fixed ranks of the
        # trace-level domains, the same for every team and seed
        self.name = {op: index_of[names[(i * 37) % len(names)]]
                     for i, op in enumerate(ops)}
        self.rpc = {op: index_of[methods[(i * 53) % len(methods)]]
                    for i, op in enumerate(ops)}
        self.cache = index_of[CACHE]
        self.store = np.array([index_of[s] for s in STORE_OF_TEAM],
                              dtype=np.int32)
        self.msg = index_of[MSG]
        self.status = {s: index_of[s] for s, _ in ob.STATUS}


def make_spans(params: dict, ids: _Ids, vals: np.ndarray, dur: np.ndarray,
               seed: int, index: int) -> dict:
    """One block's span arrays from (seed, index) and the block's
    trace-level columns: `count` int32 [N]; flat, per trace contiguous,
    in the order an ingester stores them (the head of this file):
    `parent` (index within the block, before or behind the span, -1
    root or orphan), `dur` uint32, `kind` int8, `vals` int16 [S, 6]."""
    n = len(dur)
    rng = np.random.default_rng([seed % (1 << 32), seed >> 32, index, 7])
    max_spans = int(params.get("max_spans", 512))
    # the entry's service -> its team: the copy of the application that
    # served the trace
    team = params["_team_of_id"][
        vals[:, ob.KEY_NAMES.index("service.name")].astype(np.int64)]
    status = vals[:, ob.KEY_NAMES.index("http.status_code")]

    # which template, dealt in exact shares
    shares = [float(s) for s in params["template_shares"]]
    tmpl = np.repeat(np.arange(len(TEMPLATES)), np.diff(np.round(
        np.concatenate([[0], np.cumsum(shares)]) * n).astype(int)))
    tmpl = tmpl[rng.permutation(n)]

    # the tail: in a fixed number of the read traces, sizes by strata
    reads = np.flatnonzero(tmpl < 2)
    n_tail = int(round(float(params["tail_share"]) * len(reads)))
    tail_idx = reads[rng.permutation(len(reads))[:n_tail]]
    u = (rng.permutation(n_tail) + rng.random(n_tail)) / max(1, n_tail)
    k_tail = np.zeros(n, dtype=np.int64)
    k_tail[tail_idx] = np.floor(
        float(params["tail_kmin"])
        * (1.0 - u) ** (-1.0 / float(params["tail_alpha"]))).astype(np.int64)
    n_chain = int(round(float(params["chain_share"]) * n_tail))
    chain = np.zeros(n, dtype=np.int64)
    uc = (rng.permutation(n_chain) + rng.random(n_chain)) / max(1, n_chain)
    chain[tail_idx[:n_chain]] = np.minimum(
        3 + np.floor(np.log1p(-uc) / np.log(float(params["chain_p"]))),
        int(params["chain_max"])).astype(np.int64)

    # template nodes a trace has, and its base size
    have = []
    base = np.zeros(n, dtype=np.int64)
    for t, (_, nodes, _) in enumerate(TEMPLATES):
        of_t = np.flatnonzero(tmpl == t)
        h = np.ones((len(of_t), len(nodes)), dtype=bool)
        for j, node in enumerate(nodes):
            if node[5] < 1.0:
                h[:, j] = _exact(len(of_t), node[5], rng)
        have.append((of_t, h))
        base[of_t] = h.sum(axis=1)
    # the whole tree first, in walk order (a node behind its parent, a
    # subtree a run): the cap falls on the stored order, further down
    want = base + chain + 2 * k_tail
    begin = np.concatenate([[0], np.cumsum(want)])
    S = int(begin[-1])

    parent = np.full(S, -1, dtype=np.int32)
    kind = np.zeros(S, dtype=np.int8)
    role = np.zeros(S, dtype=np.int8)           # the service, by ROLE_OF
    sv = np.full((S, len(SPAN_KEYS)), -1, dtype=np.int32)
    frac = rng.random(S) * 0.9 + 0.05
    tail_anchor = np.full(n, -1, dtype=np.int64)   # ReadPosts' position

    def fill(pos, tm, svc, knd, op, extra, root_status=None):
        kind[pos] = knd
        role[pos] = ROLES_WITH_SPANS.index(svc)
        sv[pos, K_SVC] = ids.svc[svc][tm]
        sv[pos, K_NAME] = ids.name[(svc, op)]
        if extra == "http":
            sv[pos, K_STATUS] = root_status
        elif extra == "rpc":
            sv[pos, K_RPC] = ids.rpc[(svc, op)]
            if knd == CLIENT:
                sv[pos, K_STATUS] = ids.status["200"]
        elif extra == "cache":
            sv[pos, K_DB] = ids.cache
        elif extra == "store":
            sv[pos, K_DB] = ids.store[tm]
        elif extra == "msg":
            sv[pos, K_MSG] = ids.msg

    for t, (_, nodes, anchor) in enumerate(TEMPLATES):
        of_t, h = have[t]
        rank = np.cumsum(h, axis=1) - 1            # position in the trace
        b = begin[of_t]
        for j, (par, svc, knd, op, extra, _) in enumerate(nodes):
            rows = np.flatnonzero(h[:, j])
            pos = b[rows] + rank[rows, j]
            if par >= 0:
                parent[pos] = b[rows] + rank[rows, par]
            fill(pos, team[of_t[rows]], svc, knd, op, extra,
                 root_status=status[of_t[rows]])
        if anchor is not None:
            tail_anchor[of_t] = b + rank[:, anchor]

    # the chain under ReadPosts, then the fan-out's pairs under it too
    ci = np.flatnonzero(chain)
    if len(ci):
        reps = chain[ci]
        first = begin[ci] + base[ci]
        pos = np.repeat(first, reps) + (
            np.arange(reps.sum()) - np.repeat(np.cumsum(reps) - reps, reps))
        is_first = np.repeat(np.cumsum(reps) - reps, reps) == np.arange(
            reps.sum())
        parent[pos] = np.where(is_first, np.repeat(tail_anchor[ci], reps),
                               pos - 1)
        fill(pos, np.repeat(team[ci], reps), *CHAIN)
    ti = np.flatnonzero(k_tail)
    if len(ti):
        reps = 2 * k_tail[ti]
        first = begin[ti] + base[ti] + chain[ti]
        off = np.arange(reps.sum()) - np.repeat(np.cumsum(reps) - reps, reps)
        pos = np.repeat(first, reps) + off
        parent[pos] = np.repeat(tail_anchor[ti], reps)
        tm = np.repeat(team[ti], reps)
        for half, (svc, knd, op, extra) in enumerate(TAIL_PAIR):
            sel = off % 2 == half
            fill(pos[sel], tm[sel], svc, knd, op, extra)

    # durations: the root's is the entry's, a child's a share of its
    # parent's; nodes are laid out parents first, so one pass by depth
    sdur = np.zeros(S, dtype=np.uint32)
    depth = np.full(S, -1, dtype=np.int16)
    sdur[begin[:-1]] = dur
    depth[begin[:-1]] = 0
    todo = np.flatnonzero(parent >= 0)
    levels = []
    while len(todo):
        ready = depth[parent[todo]] == len(levels)
        now = todo[ready]
        levels.append(now)
        depth[now] = len(levels)
        sdur[now] = np.floor(sdur[parent[now]] * frac[now]).astype(np.uint32)
        todo = todo[~ready]

    # errors: born on one span of the trace, carried up to the root
    err = np.flatnonzero(status == ids.status["500"])
    cur = begin[err] + np.floor(rng.random(len(err)) * want[err]).astype(
        np.int64)
    s500 = ids.status["500"]
    while len(cur):
        sv[cur, K_STATUS] = s500
        cur = parent[cur]
        cur = cur[cur >= 0]
    assert (sv >= 0).sum(axis=1).max() <= SPAN_SLOTS
    assert sv.max() <= np.iinfo(np.int16).max

    # the stored order: by trace, by the arrival of its services'
    # batches (drawn a trace), within a batch as the spans ended:
    # post-order, which a walk-order index, depth and subtree size give
    size = np.ones(S, dtype=np.int64)
    for now in reversed(levels):
        np.add.at(size, parent[now], size[now])
    tr = np.repeat(np.arange(n), want)
    ended = np.arange(S) - depth + size
    arrived = rng.random((n, len(ROLES_WITH_SPANS)))[tr, role]
    order = np.lexsort((ended, arrived, tr))
    at = np.empty(S, dtype=np.int64)
    at[order] = np.arange(S)
    parent = np.where(parent >= 0, at[np.maximum(parent, 0)], -1)[order]
    # the cap: a trace's first `max_spans` as stored; a kept span whose
    # parent went has none
    keep = np.arange(S) - begin[:-1][tr] < max_spans
    kept_at = np.cumsum(keep) - 1
    parent = parent[keep]
    has = parent >= 0
    orphan = has & ~keep[np.maximum(parent, 0)]
    parent = np.where(has & ~orphan, kept_at[np.maximum(parent, 0)],
                      -1).astype(np.int32)
    count = np.minimum(want, max_spans)
    return {"count": count.astype(np.int32), "parent": parent,
            "dur": sdur[order][keep], "kind": kind[order][keep],
            "vals": sv[order][keep].astype(np.int16),
            "depth": depth[order][keep],
            "cut": int((want > count).sum()), "orphans": int(orphan.sum()),
            "wanted": int(want.sum())}


def pack_block(vals, start, end, dur, spans: dict, table, block: int):
    """`otel_blocks.pack_block` for a block that carries spans: the
    dictionaries are the union of what entries and spans hold, and the
    container gets the span segment (`ColumnarPages.span_*`: flat entry
    index, parent as a flat span index, the kvs in 4 slots in sorted-key
    order, and each entry's run)."""
    from tempo_tpu.search.columnar import ColumnarPages, PageGeometry

    E = ob.PAGE_ENTRIES
    n, K = vals.shape
    P = -(-n // E)
    have = vals >= 0
    C = 1
    while C < int(have.sum(axis=1).max()):
        C *= 2
    sv = spans["vals"]
    shave = sv >= 0
    present = np.zeros(len(table), dtype=bool)
    present[vals[have]] = True
    present[sv[shave]] = True
    remap = (np.cumsum(present) - 1).astype(np.int32)
    key_present = have.any(axis=0)
    span_col = np.array([ob.KEY_NAMES.index(k) for k in SPAN_KEYS])
    key_present[span_col[shave.any(axis=0)]] = True
    key_remap = (np.cumsum(key_present) - 1).astype(np.int32)
    slot = np.cumsum(have, axis=1) - 1
    rows, cols = np.nonzero(have)
    kv_key = np.full((P * E, C), -1, dtype=np.int32)
    kv_val = np.full((P * E, C), -1, dtype=np.int32)
    kv_key[rows, slot[rows, cols]] = key_remap[cols]
    kv_val[rows, slot[rows, cols]] = remap[vals[rows, cols]]
    valid = np.zeros(P * E, dtype=bool)
    valid[:n] = True

    def paged(a, dtype):
        out = np.zeros(P * E, dtype=dtype)
        out[:n] = a
        return out.reshape(P, E)

    S = len(spans["parent"])
    sslot = np.cumsum(shave, axis=1) - 1
    srows, scols = np.nonzero(shave)
    span_kv_key = np.full((S, SPAN_SLOTS), -1, dtype=np.int32)
    span_kv_val = np.full((S, SPAN_SLOTS), -1, dtype=np.int32)
    span_kv_key[srows, sslot[srows, scols]] = key_remap[span_col[scols]]
    span_kv_val[srows, sslot[srows, scols]] = remap[sv[srows, scols]]
    count = spans["count"]
    begin = np.concatenate([[0], np.cumsum(count)])[:-1]

    svc_c = ob.KEY_NAMES.index("service.name")
    name_c = ob.KEY_NAMES.index("name")
    header = {
        "n_entries": n, "n_pages": P, "entries_per_page": E,
        "kv_per_entry": C, "n_keys": int(key_present.sum()),
        "n_vals": int(present.sum()), "truncated_entries": 0,
        "min_start_s": int(start.min()), "max_end_s": int(end.max()),
        "min_dur_ms": int(dur.min()), "max_dur_ms": int(dur.max()),
        "n_spans": S, "span_kv_per_entry": SPAN_SLOTS,
    }
    pages = ColumnarPages(
        geometry=PageGeometry(E, C),
        key_dict=[k for k, p in zip(ob.KEY_NAMES, key_present) if p],
        val_dict=[table[i] for i in np.flatnonzero(present)],
        kv_key=kv_key.reshape(P, E, C), kv_val=kv_val.reshape(P, E, C),
        entry_start=paged(start, np.uint32), entry_end=paged(end, np.uint32),
        entry_dur=paged(dur, np.uint32), entry_valid=valid.reshape(P, E),
        entry_root_svc=paged(remap[vals[:, svc_c]], np.int32),
        entry_root_name=paged(remap[vals[:, name_c]], np.int32),
        trace_ids=ob.trace_ids(block, P), n_entries=n, header=header,
        span_trace=np.repeat(np.arange(n, dtype=np.int32), count),
        span_parent=spans["parent"], span_dur=spans["dur"],
        span_kind=spans["kind"], span_kv_key=span_kv_key,
        span_kv_val=span_kv_val,
        entry_span_begin=paged(begin, np.int32),
        entry_span_count=paged(count, np.int32))
    return pages, present, key_present


def prepare(params: dict) -> tuple:
    """(vocab, table, gid, ids, params with the team lookup)."""
    vocab = ob.vocabulary(params)
    table = vocab["table"]
    index_of = {v: i for i, v in enumerate(table)}
    gid = {k: np.array([index_of[v] for v in vals], dtype=np.int16)
           for k, (vals, _) in vocab["domains"].items()}
    team_of_id = np.zeros(len(table), dtype=np.int64)
    for i, svc in enumerate(vocab["services"]):
        team_of_id[index_of[svc]] = i // len(ob.ROLES)
    return vocab, table, gid, _Ids(vocab, index_of), dict(
        params, _team_of_id=team_of_id)


def generate(params: dict, seed: int, backend_dir: str, pool) -> dict:
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.backend.types import (
        BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER,
    )
    from tempo_tpu.encoding.v2.compression import compress

    require_span_counters("generator otel_calltree")
    vocab, table, gid, ids, params = prepare(params)
    n_blocks, n = int(params["blocks"]), int(params["entries_per_block"])
    n_pages = -(-n // ob.PAGE_ENTRIES)
    tenant = params["tenant"]
    be = LocalBackend(backend_dir)
    K = len(ob.KEYS)
    vals_all = np.empty((n_blocks, K, n), dtype=np.int16)  # key-major
    start_all = np.empty((n_blocks, n), dtype=np.uint32)
    end_all = np.empty((n_blocks, n), dtype=np.uint32)
    dur_all = np.empty((n_blocks, n), dtype=np.uint32)
    present_all = np.zeros((n_blocks, len(table)), dtype=bool)
    key_present_all = np.zeros((n_blocks, K), dtype=bool)
    spans_all: list = [None] * n_blocks
    block_ids = [ob.block_id(params["config_name"], i, n_pages)
                 for i in range(n_blocks)]

    def one(i: int) -> tuple:
        vals, start, end, dur = ob.make_block(params, vocab, gid, seed, i)
        spans = make_spans(params, ids, vals, dur, seed, i)
        vals_all[i], start_all[i], end_all[i], dur_all[i] = (
            vals.T, start, end, dur)
        pages, present_all[i], key_present_all[i] = pack_block(
            vals, start, end, dur, spans, table, i)
        spans_all[i] = spans
        blob = compress(pages.to_bytes(), "zstd")
        hdr = dict(pages.header)
        hdr["encoding"] = "zstd"
        hdr["compressed_size"] = len(blob)
        m = BlockMeta(tenant_id=tenant, encoding="zstd",
                      block_id=block_ids[i], start_time=hdr["min_start_s"],
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
    edges = [(ROLE_OF[a], ROLE_OF[b]) for a, b in call_edges()]
    return {
        "tenant": tenant, "blocks": {tenant: n_blocks},
        "block_ids": block_ids,
        "entries": n_blocks * n, "pages": n_blocks * n_pages,
        "kv_per_entry": max(c for _, c in written),
        "disk_bytes": sum(b for b, _ in written), "table": table,
        "key_names": ob.KEY_NAMES,
        "vals": vals_all, "start": start_all, "end": end_all,
        "dur": dur_all, "present": present_all,
        "key_present": key_present_all,
        "span_key_names": SPAN_KEYS, "span_slots": SPAN_SLOTS,
        "span_count": [s["count"] for s in spans_all],
        "span_parent": [s["parent"] for s in spans_all],
        "span_dur": [s["dur"] for s in spans_all],
        "span_kind": [s["kind"] for s in spans_all],
        "span_vals": [s["vals"] for s in spans_all],
        "span_depth": [s["depth"] for s in spans_all],
        "spans": int(sum(len(s["parent"]) for s in spans_all)),
        "cut_traces": int(sum(s["cut"] for s in spans_all)),
        "orphan_spans": int(sum(s["orphans"] for s in spans_all)),
        "spans_wanted": int(sum(s["wanted"] for s in spans_all)),
        "call_edges": edges,
        "span_services": [s for s in vocab["services"]
                          if s.split("-", 1)[1] in ROLE_OF.values()],
        "vocab": {"services": vocab["services"], "teams": list(ob.TEAMS),
                  "roles": list(ob.ROLES),
                  "domains": {k: (vals, None if p is None else p.tolist())
                              for k, (vals, p) in vocab["domains"].items()}},
        "dur_ms_quantile": lambda q: ob.duration_ms_quantile(
            params, float(q)),
        "time_base": params["time_base"],
        "time_span_s": params["time_span_s"],
        "entry_of_trace_id": ob.entry_of_trace_id,
    }
