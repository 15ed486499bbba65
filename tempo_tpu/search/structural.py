"""Structural query engine: compile the IR onto the fused scan kernels.

The front half (tempo_tpu/search/ir.py) parses a typed query tree —
span-scope leaves, AND/OR/NOT, parent-child / descendant relations,
count and duration-quantile aggregates. This module is the back half,
the TiLT idiom (arxiv 2301.12030): the tree is COMPILED, not
interpreted — lowering walks the static plan descriptor at jax trace
time and emits one fused XLA computation that evaluates the whole
query as vectorized array ops over the staged columns, where the data
already lives (the Taurus near-data argument, arxiv 2506.20010):

  - **leaf predicates** reuse the scan engines' membership machinery:
    tag terms probe the block dictionaries through the SAME host
    (memmem → id ranges) and device (packed-dictionary kernel → hit
    mask) paths query compilation uses, and the kernel-side membership
    test is the same range-compare / ``mask_select_grouped`` lookup —
    bit-packed masks and packed-width entry columns (``unpack_ids`` /
    ``duration_ok``) included;
  - **structural relations** lower to vectorized joins over the
    per-trace span segments: ``child`` is one gather through the
    parent-pointer column; ``desc`` is one running max over the span
    axis, which staging lays out depth first inside every trace with
    each span's last descendant beside it (``stack_spans``,
    ``_descends`` — jit cache keys stay shape-only);
  - **aggregates** lower to segment reductions (one cumsum + two
    gathers per count, via the per-entry span-range columns) whose
    [P, E] verdicts AND into the legacy entry mask feeding the existing
    masked top-k;
  - **quantiles** lower to exact integer COUNT predicates
    (nearest-rank: ``p_q >= X  <=>  #(dur >= X) >= n - ceil(q*n) + 1``)
    so host and device agree bit-for-bit with no sorting and no floats.

``eval_host`` is the reference evaluator — plain python over
``SearchData.spans`` — used by the live/WAL scan path, the proto
fallback scan, and the differential fuzzer that pins compiled == host
byte-for-byte across every engine path.

Noop contract: ``search_structural_enabled`` off means
``structural_query()`` reads one attribute and returns None; legacy
requests take the existing byte-identical path (the noop-contract
checker registers both the gate function and the staging call sites).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, field

import numpy as np

from . import ir

# reserved in-band request tag carrying the percent-quoted compact JSON
# IR (the EXHAUSTIVE_SEARCH_TAG idiom): the structural query survives
# the frontend <-> querier SearchRequest proto round-trip and the URL
# tags encoding without a schema change. Never itself a tag predicate —
# every term-probing site excludes it alongside the exhaustive flag.
STRUCTURAL_QUERY_TAG = "x-structural-q"

_PARSE_CACHE_MAX = 256

# THE tile of the span axis (a power of two, a multiple of 128): staging
# starts every block's spans on a multiple of it (stack_spans,
# shard_span_segment), so a tag leaf looks its block's tables up once a
# tile, S / SPAN_TILE indices, and broadcasts over the tile's rows
# (_tile_leaf) where a lookup a row was three gathers with S indices a
# leaf. The price is the alignment's pad, under SPAN_TILE rows a block:
# nothing for a block of 774,000 spans, ~13 % for a group of blocks of
# 2,000, inside what the power-of-two bucket gives away anyway.
SPAN_TILE = 512


class StructuralGate:
    """Process-wide gate + knobs (the PACKING/OWNERSHIP singleton
    idiom). ``enabled`` is read ONCE per request by structural_query;
    everything else in this module only runs behind it."""

    def __init__(self) -> None:
        self.enabled = False
        self.max_spans = 512      # span rows captured per trace at ingest
        self.max_span_kvs = 16    # kv pairs captured per span at ingest
        self.max_nodes = ir.MAX_NODES  # parse-time IR size cap
        # plan-shape query stacking (search_structural_stack_enabled):
        # concurrent structural queries sharing one PLAN descriptor
        # stack along the coalescer's query axis into one fused
        # dispatch. Off (default) keeps the solo-flush behavior exactly
        self.stack_enabled = False
        # segment-aligned span sharding (search_structural_shard_spans):
        # mesh staging reshards the span segment so each trace's span
        # run lands whole on its page's shard — parent joins go
        # shard-local and span HBM per shard drops ~1/P. Off (default)
        # keeps the replicated layout exactly
        self.shard_spans = False
        # shape-bucketed cross-plan stacking
        # (search_structural_bucket_enabled): concurrent structural
        # queries whose plans canonicalize into the SAME bucket shape
        # (canonical_bucket) fuse into one dispatch even when their
        # exact plan descriptors differ — the slot-program tables carry
        # each member's active nodes, padded slots evaluate as masked
        # no-ops. Off (default) keeps exact-plan grouping only
        self.bucket_enabled = False
        # bucket tier cap: a plan whose flattened slot count (span +
        # trace slots, incl. the root-copy slot) exceeds this goes back
        # to exact-plan grouping ("still goes solo" in the docs)
        self.bucket_max_nodes = 16
        # remainder-shard mesh layout
        # (search_structural_remainder_pages): mesh staging pads the
        # page axis to the MINIMAL multiple of the shard count instead
        # of the pow2 bucket — the last shard owns the ragged tail
        # behind the static shard_tail jit descriptor. Off (default)
        # keeps the pow2 bucketing exactly
        self.remainder_pages = False
        self._parse_cache: OrderedDict = OrderedDict()
        self._parse_lock = threading.Lock()

    # ---- staging (called behind `if STRUCTURAL.enabled` guards — the
    # noop-contract GuardedCall rule pins the call-site shape) ----

    def stack_spans(self, blocks: list, E: int, pad_pages: int) -> tuple:
        """Stack the blocks' span segments for a batched staging:
        flat span arrays concatenate with per-block index remaps (trace
        index += page offset * E, parent/last/begin += span base). THE
        LAYOUT RULE has two halves. By the tile: every block's spans
        start on a multiple of SPAN_TILE and the axis pads to a power
        of two of at least one tile (shape-only jit keys), so each
        aligned tile of SPAN_TILE rows holds live rows of ONE block;
        ``span_tile_block`` names it, a row a tile, and the tag leaves
        look their block's tables up by the tile (_tile_leaf). The rows
        between a block's end and the next tile are pad rows like those
        at the axis' end (``span_trace`` -1). Inside a trace: its run
        of rows stays where the block has it and is laid out depth
        first, every span directly before its subtree (span_preorder:
        whatever order the block stores), with ``span_last`` the row of
        each span's last descendant, so a span's subtree is the run
        ``[row, span_last[row]]`` and `desc` is one running max
        (_descends). Returns the host numpy dict, or None when no
        block carries spans. What the depth-first layout cost the host
        is booked here, where it is paid: the rows that moved and the
        seconds of the sort with the permuted copies."""
        import time

        if not any(getattr(b, "has_spans", False) for b in blocks):
            return None
        from tempo_tpu.observability import metrics as obs

        tile = SPAN_TILE
        total = sum(_tile_up(b.n_spans, tile) for b in blocks
                    if getattr(b, "has_spans", False))
        S = _pow2(max(tile, total))
        Cs = max(b.span_kv_key.shape[1] for b in blocks if b.has_spans)
        cols = _empty_span_cols(S, Cs, tile)
        cols["entry_span_begin"] = np.zeros((pad_pages, E), dtype=np.int32)
        cols["entry_span_count"] = np.zeros((pad_pages, E), dtype=np.int32)
        base = 0
        page_off = 0
        for bi, b in enumerate(blocks):
            P = b.n_pages
            if getattr(b, "has_spans", False):
                n = b.n_spans
                t0 = time.perf_counter()
                perm, par, last, moved = span_preorder(
                    b.span_parent,
                    b.entry_span_begin.reshape(-1)[b.span_trace])
                moved_cols = {
                    name: getattr(b, name)[perm] if moved
                    else getattr(b, name)
                    for name in ("span_dur", "span_kind", "span_kv_key",
                                 "span_kv_val")}
                obs.structural_span_order_seconds.inc(
                    time.perf_counter() - t0)
                obs.structural_span_reorder_rows.inc(moved, moved="yes")
                obs.structural_span_reorder_rows.inc(n - moved, moved="no")
                # a trace's run keeps its place, so its rows' trace does
                cols["span_trace"][base:base + n] = \
                    b.span_trace + page_off * E
                cols["span_parent"][base:base + n] = \
                    np.where(par >= 0, par + base, -1)
                cols["span_last"][base:base + n] = last + base
                for name, col in moved_cols.items():
                    # [n] or, the kv columns, [n, the block's Cs]
                    cols[name][(slice(base, base + n),
                                *map(slice, col.shape[1:]))] = col
                cnt = b.entry_span_count
                cols["entry_span_begin"][page_off:page_off + P] = \
                    np.where(cnt > 0, b.entry_span_begin + base, 0)
                cols["entry_span_count"][page_off:page_off + P] = cnt
                end = base + _tile_up(n, tile)
                cols["span_tile_block"][base // tile:end // tile] = bi
                base = end
            page_off += P
        return cols

    def stack_group_key(self, batch, st) -> tuple | None:
        """THE plan-shape stacking gate: the coalescer's pending-group
        key for a structural query, or None — one attribute read when
        search_structural_stack_enabled is off (the caller's solo-flush
        path). Two structural queries share a key iff they target the
        same staged batch AND lowered to the identical static plan
        descriptor: the plan is the jit key, so same-plan members share
        one compiled executable and only their parameter tables differ
        — exactly the continuous-batching shape the legacy coalescer
        exploits."""
        if not self.stack_enabled:
            return None
        if self.bucket_enabled:
            bk = self.bucket_group_key(batch, st)
            if bk is not None:
                return bk
        return (id(batch), st.plan)

    def bucket_group_key(self, batch, st) -> tuple | None:
        """THE shape-bucket gate: the coalescer's pending-group key for
        a structural query under cross-plan bucketing, or None — one
        attribute read when search_structural_bucket_enabled is off
        (the caller falls back to exact-plan grouping), and None when
        the plan exceeds the bucket tier cap (it still goes solo /
        exact-plan, never a silently truncated program). Two queries
        share a bucket key iff they target the same staged batch AND
        their plans canonicalize to the identical bucket descriptor
        (canonical_bucket): the DESCRIPTOR is the jit key, member plans
        ride as dynamic per-query slot programs."""
        if not self.bucket_enabled:
            return None
        bk = canonical_bucket(st.plan, self.bucket_max_nodes)
        if bk is None:
            return None
        return (id(batch), bk)

    def remainder_pad(self, total: int, n_shards: int) -> int | None:
        """THE remainder-shard gate: the MINIMAL multiple-of-n_shards
        padded page count for a mesh staging, or None — one attribute
        read when search_structural_remainder_pages is off (the caller
        keeps the pow2 page bucketing exactly). The last shard owns the
        short chunk: the trailing pad pages all land there, described
        by the static per-shard valid length (`shard_tail`) the dist
        kernels carry in their jit key."""
        if not self.remainder_pages:
            return None
        n = max(1, int(n_shards))
        return max(n, -(-int(total) // n) * n)

    def shard_span_segment(self, span_cat: dict, n_shards: int,
                           pad_pages: int, E: int) -> dict | None:
        """THE span-sharding gate: reshard a replicated-layout span
        segment (stack_spans output) into the segment-aligned sharded
        layout, or None — one attribute read when
        search_structural_shard_spans is off, and None whenever the
        page axis does not divide evenly over the mesh (the caller
        keeps the replicated layout; still correct, just not sharded).

        Layout: the span axis becomes ``n_shards`` consecutive chunks
        of one uniform pow2 ``per_shard`` length, chunk ``s`` holding
        exactly the spans of traces whose page lands on shard ``s``
        (per-trace runs are contiguous and a trace lives on one page,
        so segments never straddle chunks). Inside a chunk stack_spans'
        layout rule holds: the chunk's rows lie block after block, each
        block on a multiple of SPAN_TILE, ``per_shard`` is at least one
        tile, and ``span_tile_block`` (split over the mesh with the
        rest) names each tile's block. Coordinates REBASE to the
        shard-local frame shard_map hands each device: ``span_trace``
        to the local entry flat index, ``span_parent`` and
        ``entry_span_begin`` and ``span_last`` to chunk-local span
        positions — the ``child`` gather and ``desc``'s running max
        then read only local rows, and per-shard span HBM is ~1/P of
        the replicated layout. A trace's rows move together and keep
        their order, so the depth-first layout inside a trace
        (stack_spans) survives the reshard."""
        if not self.shard_spans:
            return None
        if n_shards <= 1 or pad_pages % n_shards:
            return None
        tile = SPAN_TILE
        S_old = int(span_cat["span_trace"].shape[0])
        pp = pad_pages // n_shards          # pages per shard
        trace = span_cat["span_trace"]
        shard_of = np.where(trace >= 0, trace // (pp * E), -1)
        tiles_old = span_cat["span_tile_block"]
        block_of = np.repeat(tiles_old, S_old // tiles_old.shape[0])
        # old global span index -> chunk-LOCAL position (for the parent
        # and entry_span_begin rebase); -1 = dropped padding row
        local_of = np.full(S_old, -1, dtype=np.int64)
        chunks = []     # (shard, its old rows, the blocks' tiles)
        need = tile
        for s in range(n_shards):
            idx = np.flatnonzero(shard_of == s)
            if not idx.size:
                continue
            # the chunk's rows come block after block (stack_spans laid
            # them down so): a run a block, each run on a tile boundary
            blk, first, counts = np.unique(
                block_of[idx], return_index=True, return_counts=True)
            room = _tile_up(counts, tile)
            starts = np.cumsum(room) - room
            local_of[idx] = (np.repeat(starts - first, counts)
                             + np.arange(idx.size))
            chunks.append((s, idx, np.repeat(blk, room // tile)))
            need = max(need, int(room.sum()))
        per_shard = _pow2(need)
        Cs = span_cat["span_kv_key"].shape[1]
        out = _empty_span_cols(n_shards * per_shard, Cs, tile)
        for s, idx, tiles in chunks:
            dst = s * per_shard + local_of[idx]
            t0 = s * per_shard // tile
            out["span_tile_block"][t0:t0 + tiles.size] = tiles
            out["span_trace"][dst] = trace[idx] - s * pp * E
            par = span_cat["span_parent"][idx]
            safe = np.clip(par, 0, S_old - 1)
            # a parent is always the same trace (collect_span_rows
            # resolves within one trace), hence the same shard; a
            # malformed cross-shard pointer maps to -1 (no parent) —
            # the explicit shard check matters because local_of is one
            # global map, so an OTHER shard's local index would
            # otherwise rebase to a wrong in-chunk row
            out["span_parent"][dst] = np.where(
                (par >= 0) & (shard_of[safe] == s),
                local_of[safe], -1).astype(np.int32)
            # a span's last descendant is a row of its own trace
            out["span_last"][dst] = local_of[span_cat["span_last"][idx]]
            for name in ("span_dur", "span_kind", "span_kv_key",
                         "span_kv_val"):
                out[name][dst] = span_cat[name][idx]
        begin = span_cat["entry_span_begin"]
        count = span_cat["entry_span_count"]
        safe_b = np.clip(begin, 0, S_old - 1)
        out["entry_span_begin"] = np.where(
            count > 0, local_of[safe_b], 0).astype(np.int32)
        out["entry_span_count"] = count
        return out


STRUCTURAL = StructuralGate()


def configure(enabled: bool | None = None, max_spans: int | None = None,
              max_span_kvs: int | None = None,
              stack_enabled: bool | None = None,
              shard_spans: bool | None = None,
              bucket_enabled: bool | None = None,
              bucket_max_nodes: int | None = None,
              remainder_pages: bool | None = None) -> StructuralGate:
    """Apply TempoDBConfig.search_structural_* to the process gate (most
    recent TempoDB wins — the PACKING/OWNERSHIP idiom)."""
    if enabled is not None:
        STRUCTURAL.enabled = bool(enabled)
    if max_spans is not None:
        STRUCTURAL.max_spans = max(1, int(max_spans))
    if max_span_kvs is not None:
        STRUCTURAL.max_span_kvs = max(1, int(max_span_kvs))
    if stack_enabled is not None:
        STRUCTURAL.stack_enabled = bool(stack_enabled)
    if shard_spans is not None:
        STRUCTURAL.shard_spans = bool(shard_spans)
    if bucket_enabled is not None:
        STRUCTURAL.bucket_enabled = bool(bucket_enabled)
    if bucket_max_nodes is not None:
        STRUCTURAL.bucket_max_nodes = max(2, int(bucket_max_nodes))
    if remainder_pages is not None:
        STRUCTURAL.remainder_pages = bool(remainder_pages)
    return STRUCTURAL


def structural_query(req) -> "ir.TraceExpr | None":
    """THE gate: the request's parsed structural IR, or None — one
    attribute read (plus one tag-membership test) when
    search_structural_enabled is off, one dict get when the request
    carries no structural tag. A request CARRYING the tag against a
    disabled gate is refused as a client error at this shared altitude
    — every transport (HTTP, gRPC search_recent/search_block/
    search_blocks, live/WAL scans) must answer 400/INVALID_ARGUMENT,
    never a silent legacy-scan superset. Parse results memoize by the
    raw quoted form (dashboards repeat their queries verbatim); a
    malformed value that bypassed API validation surfaces as
    InvalidArgument too, never a 500 from deep in compile."""
    if not STRUCTURAL.enabled:
        if STRUCTURAL_QUERY_TAG in req.tags:
            from tempo_tpu.api.params import InvalidArgument

            raise InvalidArgument(
                "structural queries disabled "
                "(storage.search_structural_enabled: true enables)")
        return None
    raw = req.tags.get(STRUCTURAL_QUERY_TAG, "")
    if not raw:
        return None
    with STRUCTURAL._parse_lock:
        hit = STRUCTURAL._parse_cache.get(raw)
        if hit is not None:
            STRUCTURAL._parse_cache.move_to_end(raw)
            return hit
    try:
        expr = ir.parse_quoted(raw)
    except ir.IRSyntaxError as e:
        from tempo_tpu.api.params import InvalidArgument

        raise InvalidArgument(f"bad structural query: {e}") from None
    with STRUCTURAL._parse_lock:
        STRUCTURAL._parse_cache[raw] = expr
        while len(STRUCTURAL._parse_cache) > _PARSE_CACHE_MAX:
            STRUCTURAL._parse_cache.popitem(last=False)
    return expr


def attach_query(req, expr: "ir.TraceExpr") -> None:
    """Stow an IR tree on a request (the API layer's parse product):
    canonical compact JSON, percent-quoted, in the reserved tag."""
    req.tags[STRUCTURAL_QUERY_TAG] = ir.quote(ir.to_json(expr))


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _tile_up(n, tile: int):
    """`n` (a count, or an array of them) rounded up to whole tiles."""
    return -(-n // tile) * tile


def _empty_span_cols(S: int, Cs: int, tile: int) -> dict:
    """The span-axis columns of a staged segment, all pad rows: `S`
    rows of `Cs` kv slots and a block a tile of `tile` rows."""
    return {
        "span_trace": np.full(S, -1, dtype=np.int32),
        "span_parent": np.full(S, -1, dtype=np.int32),
        "span_last": np.full(S, -1, dtype=np.int32),
        "span_tile_block": np.zeros(S // tile, dtype=np.int32),
        "span_dur": np.zeros(S, dtype=np.uint32),
        "span_kind": np.zeros(S, dtype=np.int8),
        "span_kv_key": np.full((S, Cs), -1, dtype=np.int32),
        "span_kv_val": np.full((S, Cs), -1, dtype=np.int32),
    }


def _ancestor_counts(parent: np.ndarray) -> tuple:
    """(depth, stuck, jump) of a parent column (row indices, -1 none):
    `depth` each row's count of proper ancestors, by pointer doubling
    over the rows that still climb (log2 of the deepest chain trips,
    each over fewer rows than the last); `stuck` the rows whose chain
    never ends, those on a loop of parents and those hanging off one,
    and `jump[stuck]` rows ON the loops, every one of them."""
    n = int(parent.shape[0])
    depth = (parent >= 0).astype(np.int32)
    jump = parent.astype(np.int32, copy=True)
    live = np.flatnonzero(jump >= 0)
    # 2^trips > n bounds every chain that ends
    for _ in range(n.bit_length()):
        if not live.size:
            break
        j = jump[live]
        step, nxt = depth[j], jump[j]
        depth[live] += step
        jump[live] = nxt
        live = live[nxt >= 0]
    return depth, live, jump


def cut_parent_loops(parent: np.ndarray) -> tuple:
    """THE rule for a malformed parent column, applied wherever the
    column is READ (staging before it sorts, eval_host before it
    walks), never to the stored bytes: a loop of parents of any length
    is cut at its first span in stored order, which then has no parent
    (collect_span_rows' rule for a span that names itself, extended);
    the spans hanging off the loop keep theirs. Returns (the column,
    the same array where it holds no loop; each row's count of proper
    ancestors)."""
    depth, stuck, jump = _ancestor_counts(parent)
    if not stuck.size:
        return parent, depth
    parent = parent.copy()
    seen: set = set()
    # ascending, so the first row met of a loop is its first stored
    for r in np.unique(jump[stuck]).tolist():
        if r in seen:
            continue
        p, parent[r] = int(parent[r]), -1
        while p != r:
            seen.add(p)
            p = int(parent[p])
    return parent, _ancestor_counts(parent)[0]


def _has_parent_loop(parent: list) -> bool:
    """Whether a walk up `parent` (one trace's, -1 none) ever comes
    back to a span it passed: every span is visited once."""
    walk = [0] * len(parent)    # the walk that first met the span
    for i in range(len(parent)):
        p = i
        while p >= 0 and not walk[p]:
            walk[p] = i + 1
            p = parent[p]
        if p >= 0 and walk[p] == i + 1:
            return True
    return False


def span_preorder(parent: np.ndarray, run_begin: np.ndarray) -> tuple:
    """The depth-first layout of one block's spans: `parent` [n] the
    block's parent column (rows of the block, -1 none), `run_begin` [n]
    the first row of each span's trace (a trace's rows are one run).
    Returns (perm, parent, last, moved): `perm[new row]` the stored row
    that moves there, `parent` and `last` the re-pointed parent and the
    last descendant of each NEW row (its own row for a leaf), `moved`
    how many rows are not where they were stored. Every trace's run
    stays where it is; inside it every span lies directly before its
    subtree, the parentless spans (roots, and spans whose parent the
    ingest cap cut) in stored order, siblings in stored order. Loops
    are cut first (cut_parent_loops) and a parent in another trace is
    no parent. Vectorised by level, not walked by trace: depths, then
    subtree sizes bottom-up, then positions top-down as the parent's
    position + 1 + the sizes of the earlier siblings."""
    n = int(parent.shape[0])
    rows = np.arange(n, dtype=np.int32)
    if not n:
        return rows, rows, rows, 0
    safe = np.clip(parent, 0, n - 1)
    parent = np.where((parent >= 0) & (run_begin[safe] == run_begin),
                      safe, -1).astype(np.int32)
    parent, depth = cut_parent_loops(parent)
    # rows by (depth, parent, stored row): siblings side by side in
    # stored order, level after level
    by_parent = np.argsort(parent, kind="stable")
    deepest = int(depth.max())
    d = depth[by_parent]
    order = by_parent[np.argsort(
        d.astype(np.uint16) if deepest < 1 << 16 else d, kind="stable")]
    lo = np.concatenate([[0], np.cumsum(np.bincount(depth))])
    par_o = parent[order]
    first = np.ones(n, dtype=bool)      # of its siblings
    first[1:] = par_o[1:] != par_o[:-1]
    size = np.ones(n, dtype=np.int32)
    for lv in range(deepest, 0, -1):
        a, b = lo[lv], lo[lv + 1]
        starts = np.flatnonzero(first[a:b])
        size[par_o[a:b][starts]] += np.add.reduceat(size[order[a:b]],
                                                    starts)
    pos = np.empty(n, dtype=np.int32)
    for lv in range(deepest + 1):
        a, b = lo[lv], lo[lv + 1]
        level = order[a:b]
        if lv:
            head, start = first[a:b], pos[par_o[a:b]] + 1
        else:
            # the parentless spans of a trace, from its run's first row
            start = run_begin[level]
            head = np.ones(b - a, dtype=bool)
            head[1:] = start[1:] != start[:-1]
        before = np.cumsum(size[level]) - size[level]
        pos[level] = start + before - np.maximum.accumulate(
            np.where(head, before, 0))
    perm = np.empty(n, dtype=np.int32)
    perm[pos] = rows
    new_parent = np.full(n, -1, dtype=np.int32)
    has = parent >= 0
    new_parent[pos[has]] = pos[parent[has]]
    last = np.empty(n, dtype=np.int32)
    last[pos] = pos + size - 1
    return perm, new_parent, last, int((pos != rows).sum())


# ---------------------------------------------------------------------------
# compilation: IR -> (static plan descriptor, dynamic parameter tables)


@dataclass
class CompiledStructural:
    """One query's compiled structural predicate against one staged
    batch: ``plan`` is the STATIC descriptor (nested tuples of ops and
    leaf indices — part of every consuming kernel's jit key, exactly
    like the packed-residency ``widths``), the tables are dynamic
    arrays (thresholds, per-block term ids, value ranges / probe
    masks), so two queries with the same SHAPE of plan share one
    compiled executable and only the parameters change."""

    plan: tuple
    term_keys: np.ndarray | None      # int32 [B, T]
    val_ranges: np.ndarray | None     # int32 [B, T, R, 2]
    val_hits: object = None           # device [G, T, Vm] (bool/packed)
    block_group: np.ndarray | None = None   # int32 [B]
    dur_params: np.ndarray | None = None    # uint32 [D, 2]
    kind_params: np.ndarray | None = None   # int32 [K]
    agg_params: np.ndarray | None = None    # uint32 [A, 3]
    # cost-model registration: node id (preorder) -> estimated bytes
    # touched on device; the planner's live scan rate turns these into
    # predicted seconds, and measured kernel time apportions across
    # them for the explain tree (docs/search-structural-queries.md)
    node_bytes: dict = field(default_factory=dict)
    node_info: list = field(default_factory=list)  # (nid, op, detail)

    def tables(self) -> tuple:
        """The dynamic-argument pytree every kernel receives."""
        return (self.term_keys, self.val_ranges, self.val_hits,
                self.block_group, self.dur_params, self.kind_params,
                self.agg_params)

    def device_tables(self, mesh=None):
        """Tables as device arrays, uploaded once per compiled query
        (the query_device_params idiom — every re-put is a separate
        host→device transfer), to where launches over `mesh` read
        them (None: the default device)."""
        return _device_tables_cached(self, self.tables(), mesh)

    def shape_sig(self) -> tuple:
        """Jit-cache contribution: the plan IS shape (static), plus the
        dynamic tables' shapes/dtypes."""
        def sig(t):
            return None if t is None else (tuple(t.shape), str(t.dtype))
        return (self.plan,) + tuple(sig(t) for t in self.tables())

    def weight(self) -> int:
        """Apportionment weight of this predicate's dynamic tables —
        added to the legacy table rows when a fused dispatch's measured
        stage times split across members (query_stats.apportion)."""
        return int(sum(int(t.size) for t in self.tables()
                       if t is not None))

    def explain(self, measured_device_s: float | None = None,
                rate_s_per_byte: float | None = None) -> dict:
        """The compiled plan tree for ?explain=1: per node the op, its
        parameters, estimated cost, and — when a measured kernel total
        is given — its apportioned share of the real device-seconds
        (cost-model-weighted: one fused kernel cannot be timed
        per-node, so the conserved split follows the same per-byte
        model the planner calibrates)."""
        from . import planner

        total_bytes = max(1, sum(self.node_bytes.values()))
        out: dict = {"nodes": []}
        for nid, op, detail in self.node_info:
            nb = self.node_bytes.get(nid, 0)
            rate = (rate_s_per_byte if rate_s_per_byte is not None
                    else planner.PLANNER.rate("scan", nb))
            node = {"id": nid, "op": op, "est_bytes": int(nb),
                    "est_ms": round(nb * rate * 1e3, 6)}
            if detail:
                node["detail"] = detail
            if measured_device_s is not None:
                node["device_ms"] = round(
                    measured_device_s * (nb / total_bytes) * 1e3, 6)
            out["nodes"].append(node)
        return out


@dataclass
class StackedStructural:
    """Q same-plan compiled predicates stacked along the coalescer's
    query axis: ONE static plan (shared jit key — plan equality is what
    the stacking group is keyed on) and 7 dynamic tables with a leading
    [Q] axis, padded to the group max where members may legitimately
    differ (value-range width R; probe-mask G/Vm). Pad query lanes copy
    member 0's tables — always-valid values on lanes the legacy pad
    predicate (empty duration window) already forces all-false."""

    plan: tuple
    tables: tuple            # 7 leaves, each [Q, ...] or None
    n_queries: int

    def device_tables(self, mesh=None):
        return _device_tables_cached(self, self.tables, mesh)

    def shape_sig(self) -> tuple:
        def sig(t):
            return None if t is None else (tuple(t.shape), str(t.dtype))
        return (self.plan,) + tuple(sig(t) for t in self.tables)


def stack_structural(sts: list, pad_q: int) -> StackedStructural:
    """Stack same-plan compiled predicates along a new leading query
    axis (pad_q = the coalescer's pow2 query count). All members MUST
    share one plan descriptor (the stack_group_key contract); value
    ranges pad to the pow2 group max with the empty [1, 0] range, and
    probe masks pad to the group (G, Vm) max with all-false rows behind
    an all -1 block_group — members that compiled through the host
    range path never read them, exactly like stack_queries' legacy
    probe stacking."""
    import jax.numpy as jnp

    from . import packing

    plan = sts[0].plan
    for st in sts[1:]:
        if st.plan != plan:
            raise StructuralCompileError(
                "stacked structural members must share one plan")
    Qn = len(sts)

    def lane(i: int):
        # pad lanes replay member 0: valid parameters on dead lanes
        return sts[i] if i < Qn else sts[0]

    def stack_plain(name: str):
        rows = [getattr(lane(i), name) for i in range(pad_q)]
        if rows[0] is None:
            return None
        return np.stack(rows)

    # val_ranges: same (B, T) under one plan, R pads to the pow2 max
    vr0 = sts[0].val_ranges
    val_ranges = None
    if vr0 is not None:
        R = _pow2(max(st.val_ranges.shape[2] for st in sts))
        B, T = vr0.shape[0], vr0.shape[1]
        val_ranges = np.tile(np.array([1, 0], dtype=np.int32),
                             (pad_q, B, T, R, 1))
        for qi in range(pad_q):
            vr = lane(qi).val_ranges
            val_ranges[qi, :, :, :vr.shape[2]] = vr
    # probe product: mixed device/host members stack like stack_queries
    # — zero masks + all -1 group rows for host-path lanes
    val_hits = block_group = None
    if any(st.val_hits is not None for st in sts):
        hits = {id(st): st.val_hits for st in sts
                if st.val_hits is not None}
        if any(packing.is_packed_mask(h) for h in hits.values()):
            hits = {k: packing.pack_mask_words(h)
                    for k, h in hits.items()}
        Gm = max(int(h.shape[0]) for h in hits.values())
        Tm = max(int(h.shape[1]) for h in hits.values())
        Vm = max(int(h.shape[2]) for h in hits.values())
        dt = next(iter(hits.values())).dtype
        zero = jnp.zeros((Gm, Tm, Vm), dtype=dt)
        B = sts[0].term_keys.shape[0]
        block_group = np.full((pad_q, B), -1, dtype=np.int32)
        rows = []
        for qi in range(pad_q):
            st = lane(qi)
            if st.val_hits is None or qi >= Qn:
                rows.append(zero)
                continue
            h = hits[id(st)]
            rows.append(jnp.pad(h, ((0, Gm - h.shape[0]),
                                    (0, Tm - h.shape[1]),
                                    (0, Vm - h.shape[2]))))
            block_group[qi] = st.block_group
        val_hits = jnp.stack(rows)                 # [Q, Gm, Tm, Vm]
    return StackedStructural(
        plan=plan,
        tables=(stack_plain("term_keys"), val_ranges, val_hits,
                block_group, stack_plain("dur_params"),
                stack_plain("kind_params"), stack_plain("agg_params")),
        n_queries=Qn)


# ---------------------------------------------------------------------------
# shape-bucketed cross-plan stacking: canonicalize heterogeneous plans
# into a small static family of bucket shapes so the coalescer fuses
# mixed-plan concurrent queries into ONE dispatch. The bucket
# descriptor ("bucket", NS, NT, has_rel) replaces the exact plan in the
# jit key; each member's exact plan lowers to a per-query int32 slot
# PROGRAM carried in the dynamic tables (the "active-node mask" — pad
# slots are opcode 0 and unreachable from the result slot, so fused
# results stay byte-identical to solo execution).

# span-program opcodes (row = [opcode, a, b, 0]; a/b are table indices
# for leaves, 1-based register indices for combinators — register 0 is
# the dummy all-false register)
_SOP = {"tag": 1, "dur": 2, "kind": 3, "and": 4, "or": 5, "not": 6,
        "child": 7, "desc": 8}
# trace-program opcodes (row = [opcode, a, b, c]; for aggregates a is
# the 1-based SPAN register, b the agg_params row, c the compare code)
_TOP = {"ttag": 1, "tdur": 2, "exists": 3, "count": 4, "q": 5,
        "and": 6, "or": 7, "not": 8}
_CMPC = {">": 0, ">=": 1, "<": 2, "<=": 3, "==": 4, "!=": 5}


def _flatten_span(plan: tuple, rows: list) -> int:
    """Postorder-flatten a span plan into program rows; returns the
    node's 1-based result register. N-ary and/or binarize into chains
    (bit-identical for booleans)."""
    op = plan[0]
    if op in ("tag", "dur", "kind"):
        rows.append([_SOP[op], plan[2], 0, 0])
        return len(rows)
    if op in ("and", "or"):
        r = _flatten_span(plan[2][0], rows)
        for sub in plan[2][1:]:
            r2 = _flatten_span(sub, rows)
            rows.append([_SOP[op], r, r2, 0])
            r = len(rows)
        return r
    if op == "not":
        r = _flatten_span(plan[2], rows)
        rows.append([_SOP["not"], r, 0, 0])
        return len(rows)
    if op in ("child", "desc"):
        ra = _flatten_span(plan[2], rows)
        rb = _flatten_span(plan[3], rows)
        rows.append([_SOP[op], ra, rb, 0])
        return len(rows)
    raise StructuralCompileError(f"bad span plan op {op!r}")


def _flatten_trace(plan: tuple, trows: list, srows: list) -> int:
    op = plan[0]
    if op in ("ttag", "tdur"):
        trows.append([_TOP[op], plan[2], 0, 0])
        return len(trows)
    if op == "exists":
        sr = _flatten_span(plan[2], srows)
        trows.append([_TOP["exists"], sr, 0, 0])
        return len(trows)
    if op in ("count", "q"):
        sr = _flatten_span(plan[4], srows)
        trows.append([_TOP[op], sr, plan[3], _CMPC[plan[2]]])
        return len(trows)
    if op in ("and", "or"):
        r = _flatten_trace(plan[2][0], trows, srows)
        for sub in plan[2][1:]:
            r2 = _flatten_trace(sub, trows, srows)
            trows.append([_TOP[op], r, r2, 0])
            r = len(trows)
        return r
    if op == "not":
        r = _flatten_trace(plan[2], trows, srows)
        trows.append([_TOP["not"], r, 0, 0])
        return len(trows)
    raise StructuralCompileError(f"bad trace plan op {op!r}")


def _flatten_plan(plan: tuple) -> tuple[list, list]:
    """Flatten an exact plan into (span_rows, trace_rows). The final
    trace row is always a root copy — OR(root, root), the boolean
    identity — so the result register is STATICALLY the last trace
    slot whatever the member's real shape (stack_bucketed keeps it at
    slot NT-1 with pad rows in between)."""
    srows: list = []
    trows: list = []
    root = _flatten_trace(plan, trows, srows)
    trows.append([_TOP["or"], root, root, 0])
    return srows, trows


def canonical_bucket(plan: tuple, max_nodes: int) -> tuple | None:
    """Canonicalize an exact plan into its bucket-shape descriptor
    ``("bucket", NS, NT, has_rel)``: NS/NT are the pow2 slot tiers of
    the flattened span/trace programs (NT includes the root-copy
    slot), has_rel marks the child/desc machinery (relation plans
    bucket separately from relation-free ones — fusing them would make
    every member pay the join arms). Returns None when the
    flattened slot count exceeds ``max_nodes``: the plan "still goes
    solo", i.e. falls back to exact-plan grouping."""
    try:
        srows, trows = _flatten_plan(plan)
    except (StructuralCompileError, IndexError, KeyError, TypeError):
        return None
    if len(srows) + len(trows) > max(2, int(max_nodes)):
        return None
    NS = _pow2(len(srows)) if srows else 0
    NT = _pow2(len(trows))
    has_rel = any(r[0] in (_SOP["child"], _SOP["desc"]) for r in srows)
    return ("bucket", NS, NT, bool(has_rel))


@dataclass
class BucketedStructural:
    """Q mixed-plan compiled predicates fused under ONE bucket
    descriptor: ``plan`` is the ("bucket", NS, NT, has_rel) jit key and
    ``tables`` carries NINE dynamic leaves — the 7 standard parameter
    tables with a leading [Q] axis (padded to the group max exactly
    like StackedStructural) plus the per-query span/trace slot programs
    ([Q, NS, 4] / [Q, NT, 4] int32). Member programs index only their
    OWN padded tables, so member-local indices are always in range."""

    plan: tuple
    tables: tuple            # 9 leaves, each [Q, ...] or None
    n_queries: int
    active_nodes: int = 0    # sum of members' real (unpadded) slots
    slot_nodes: int = 0      # n_queries * (NS + NT) bucket slots

    def device_tables(self, mesh=None):
        return _device_tables_cached(self, self.tables, mesh)

    def shape_sig(self) -> tuple:
        def sig(t):
            return None if t is None else (tuple(t.shape), str(t.dtype))
        return (self.plan,) + tuple(sig(t) for t in self.tables)


def stack_bucketed(sts: list, pad_q: int,
                   desc: tuple) -> BucketedStructural:
    """Stack mixed-plan compiled predicates under one bucket descriptor
    (every member's canonical_bucket MUST equal ``desc`` — the
    bucket_group_key contract). Parameter tables pad to the group max
    with inert rows a member's program never references (term_keys -1,
    val_ranges [1, 0], agg_params (0, 1, 0) so the computed-but-
    unselected quantile arm never divides by zero); the probe product
    mirrors stack_structural. Pad query lanes replay member 0."""
    import jax.numpy as jnp

    from . import packing

    _op, NS, NT, _rel = desc
    Qn = len(sts)
    active = 0
    sprogs = []
    tprogs = []
    for st in sts:
        srows, trows = _flatten_plan(st.plan)
        active += len(srows) + len(trows)
        sp = np.zeros((max(1, NS), 4), dtype=np.int32)
        if srows:
            sp[:len(srows)] = np.asarray(srows, dtype=np.int32)
        tp = np.zeros((NT, 4), dtype=np.int32)
        body = trows[:-1]
        if body:
            tp[:len(body)] = np.asarray(body, dtype=np.int32)
        tp[NT - 1] = trows[-1]       # root copy -> the result slot
        sprogs.append(sp)
        tprogs.append(tp)

    def lane(i: int):
        return sts[i] if i < Qn else sts[0]

    def lane_prog(progs, i: int):
        return progs[i] if i < Qn else progs[0]

    # term_keys [B, T] -> [Q, B, Tm] (-1 = no term); members that
    # compiled without tag leaves get all -1 rows
    term_keys = val_ranges = None
    if any(st.term_keys is not None for st in sts):
        B = next(st.term_keys.shape[0] for st in sts
                 if st.term_keys is not None)
        Tm = _pow2(max(st.term_keys.shape[1] for st in sts
                       if st.term_keys is not None))
        Rm = _pow2(max(st.val_ranges.shape[2] for st in sts
                       if st.val_ranges is not None))
        term_keys = np.full((pad_q, B, Tm), -1, dtype=np.int32)
        val_ranges = np.tile(np.array([1, 0], dtype=np.int32),
                             (pad_q, B, Tm, Rm, 1))
        for qi in range(pad_q):
            st = lane(qi)
            if st.term_keys is None:
                continue
            term_keys[qi, :, :st.term_keys.shape[1]] = st.term_keys
            vr = st.val_ranges
            val_ranges[qi, :, :vr.shape[1], :vr.shape[2]] = vr

    def stack_padded(name: str, width: tuple, fill) -> np.ndarray | None:
        rows = [getattr(lane(i), name) for i in range(pad_q)]
        if all(r is None for r in rows):
            return None
        Nm = _pow2(max(r.shape[0] for r in rows if r is not None))
        dt = next(r for r in rows if r is not None).dtype
        out = np.empty((pad_q, Nm) + width, dtype=dt)
        out[...] = fill
        for qi, r in enumerate(rows):
            if r is not None:
                out[qi, :r.shape[0]] = r
        return out

    dur_params = stack_padded("dur_params", (2,), 0)
    kind_params = stack_padded("kind_params", (), 0)
    agg_params = stack_padded("agg_params", (3,),
                              np.array([0, 1, 0], dtype=np.uint32))
    # probe product: same zero-mask + all -1 group-row padding as
    # stack_structural for host-path / probe-less members
    val_hits = block_group = None
    if any(st.val_hits is not None for st in sts):
        hits = {id(st): st.val_hits for st in sts
                if st.val_hits is not None}
        if any(packing.is_packed_mask(h) for h in hits.values()):
            hits = {k: packing.pack_mask_words(h)
                    for k, h in hits.items()}
        Gm = max(int(h.shape[0]) for h in hits.values())
        Tm2 = max(int(h.shape[1]) for h in hits.values())
        Vm = max(int(h.shape[2]) for h in hits.values())
        dt = next(iter(hits.values())).dtype
        zero = jnp.zeros((Gm, Tm2, Vm), dtype=dt)
        B = next(st.term_keys.shape[0] for st in sts
                 if st.term_keys is not None)
        block_group = np.full((pad_q, B), -1, dtype=np.int32)
        rows = []
        for qi in range(pad_q):
            st = lane(qi)
            if st.val_hits is None or qi >= Qn:
                rows.append(zero)
                continue
            h = hits[id(st)]
            rows.append(jnp.pad(h, ((0, Gm - h.shape[0]),
                                    (0, Tm2 - h.shape[1]),
                                    (0, Vm - h.shape[2]))))
            block_group[qi] = st.block_group
        val_hits = jnp.stack(rows)                 # [Q, Gm, Tm, Vm]
    span_prog = np.stack([lane_prog(sprogs, i) for i in range(pad_q)])
    trace_prog = np.stack([lane_prog(tprogs, i) for i in range(pad_q)])
    return BucketedStructural(
        plan=desc,
        tables=(term_keys, val_ranges, val_hits, block_group,
                dur_params, kind_params, agg_params,
                span_prog, trace_prog),
        n_queries=Qn, active_nodes=active,
        slot_nodes=Qn * (NS + NT))


def _device_tables_cached(owner, tables: tuple, mesh=None) -> tuple:
    """One upload per compiled/stacked predicate, memoized on the owner
    (shared by CompiledStructural and StackedStructural so the upload
    path has exactly one implementation) under the placement it was
    made for. On a mesh the tables go to every device of it, as the
    dist kernels' in_specs want them (parallel.mesh.put_replicated)."""
    cached = getattr(owner, "_device_tables", None)
    if cached is not None and cached[0] == mesh:
        return cached[1]
    if mesh is None:
        import jax.numpy as jnp

        placed = tuple((jnp.asarray(t) if isinstance(t, np.ndarray) else t)
                       for t in tables)
    else:
        from tempo_tpu.parallel.mesh import put_replicated

        placed = put_replicated(mesh, tuple(tables))
    owner._device_tables = (mesh, placed)
    return placed


class StructuralCompileError(ValueError):
    """Internal compile failure — the API layer maps it to 400 like a
    parse error (it is always rooted in the query, never the corpus)."""


def compile_structural(expr: "ir.TraceExpr", blocks: list,
                       cache_on=None, staged_dicts: dict | None = None,
                       host_only: bool = False,
                       entry_kv_slots: int = 1) -> CompiledStructural:
    """Lower an IR tree against a batch's blocks: collect leaves, probe
    every distinct dictionary ONCE per leaf set (reusing the host memmem
    / device packed-probe paths with the exhaustive contract — leaves
    must never block-prune, an unmatched leaf is simply False for that
    block), and assemble block-indexed tables exactly like
    compile_multi does for the legacy terms. ``host_only`` is the
    breaker/host-route contract: no staged dictionary is consulted and
    the product carries host range tables only."""
    leaves = _LeafCollector()
    plan = leaves.lower_trace(expr)
    B = max(1, len(blocks))

    term_keys = val_ranges = val_hits = block_group = None
    if leaves.terms:
        term_keys, val_ranges, val_hits, block_group = _assemble_terms(
            leaves.terms, blocks, cache_on=cache_on,
            staged_dicts=staged_dicts, host_only=host_only)
    dur_params = (np.asarray(leaves.durs, dtype=np.uint32)
                  if leaves.durs else None)
    kind_params = (np.asarray(leaves.kinds, dtype=np.int32)
                   if leaves.kinds else None)
    agg_params = (np.asarray(leaves.aggs, dtype=np.uint32)
                  if leaves.aggs else None)

    cs = CompiledStructural(
        plan=plan, term_keys=term_keys, val_ranges=val_ranges,
        val_hits=val_hits, block_group=block_group,
        dur_params=dur_params, kind_params=kind_params,
        agg_params=agg_params, node_info=leaves.node_info)
    # cost-model registration happens against batch-independent proxies
    # here; the engines refresh with real staged sizes at dispatch
    cs.node_bytes = plan_node_bytes(
        plan, n_spans=sum(getattr(b, "n_spans", 0) for b in blocks),
        n_entries=sum(
            getattr(b, "n_pages", 1)
            * getattr(getattr(b, "geometry", None), "entries_per_page",
                      1024)
            for b in blocks),
        span_kv_slots=max(
            [b.span_kv_key.shape[1] for b in blocks
             if getattr(b, "has_spans", False)] or [1]),
        entry_kv_slots=entry_kv_slots)
    _ = B
    return cs


class _LeafCollector:
    """IR walk: dedupe leaves into parameter tables and emit the static
    plan descriptor. Node ids are preorder positions (stable across
    host and device, and across sub-requests of one query — the
    frontend merges explain nodes by id)."""

    def __init__(self) -> None:
        self.terms: list[tuple[str, str]] = []
        self._term_idx: dict[tuple[str, str], int] = {}
        self.durs: list[tuple[int, int]] = []
        self._dur_idx: dict[tuple[int, int], int] = {}
        self.kinds: list[int] = []
        self._kind_idx: dict[int, int] = {}
        self.aggs: list[tuple[int, int, int]] = []
        self.node_info: list[tuple[int, str, str]] = []
        self._next_id = 0

    def _nid(self, op: str, detail: str = "") -> int:
        nid = self._next_id
        self._next_id += 1
        self.node_info.append((nid, op, detail))
        return nid

    def _term(self, key: str, value: str) -> int:
        t = (key, value)
        i = self._term_idx.get(t)
        if i is None:
            i = self._term_idx[t] = len(self.terms)
            self.terms.append(t)
        return i

    def _dur(self, lo: int, hi: int) -> int:
        d = (lo, hi)
        i = self._dur_idx.get(d)
        if i is None:
            i = self._dur_idx[d] = len(self.durs)
            self.durs.append(d)
        return i

    def _kind(self, k: int) -> int:
        i = self._kind_idx.get(k)
        if i is None:
            i = self._kind_idx[k] = len(self.kinds)
            self.kinds.append(k)
        return i

    def lower_span(self, e: "ir.SpanExpr") -> tuple:
        if isinstance(e, ir.SpanTag):
            nid = self._nid("span.tag", f"{e.key}~{e.value}")
            return ("tag", nid, self._term(e.key, e.value))
        if isinstance(e, ir.SpanDur):
            nid = self._nid("span.dur", f"[{e.lo_ms},{e.hi_ms}]ms")
            return ("dur", nid, self._dur(e.lo_ms, e.hi_ms))
        if isinstance(e, ir.SpanKind):
            nid = self._nid("span.kind", str(e.kind))
            return ("kind", nid, self._kind(e.kind))
        if isinstance(e, ir.SpanAnd):
            nid = self._nid("span.and")
            return ("and", nid, tuple(self.lower_span(a) for a in e.args))
        if isinstance(e, ir.SpanOr):
            nid = self._nid("span.or")
            return ("or", nid, tuple(self.lower_span(a) for a in e.args))
        if isinstance(e, ir.SpanNot):
            nid = self._nid("span.not")
            return ("not", nid, self.lower_span(e.arg))
        if isinstance(e, ir.ChildOf):
            nid = self._nid("child", "parent-pointer join")
            return ("child", nid, self.lower_span(e.parent),
                    self.lower_span(e.child))
        if isinstance(e, ir.DescOf):
            nid = self._nid("desc", "running-max ancestor join")
            return ("desc", nid, self.lower_span(e.anc),
                    self.lower_span(e.span))
        raise StructuralCompileError(
            f"unknown span node {type(e).__name__}")

    def lower_trace(self, e: "ir.TraceExpr") -> tuple:
        if isinstance(e, ir.TraceTag):
            nid = self._nid("trace.tag", f"{e.key}~{e.value}")
            return ("ttag", nid, self._term(e.key, e.value))
        if isinstance(e, ir.TraceDur):
            nid = self._nid("trace.dur", f"[{e.lo_ms},{e.hi_ms}]ms")
            return ("tdur", nid, self._dur(e.lo_ms, e.hi_ms))
        if isinstance(e, ir.Exists):
            nid = self._nid("exists", "segment reduce")
            return ("exists", nid, self.lower_span(e.of))
        if isinstance(e, ir.Count):
            nid = self._nid("count", f"{e.op} {e.n}")
            ai = len(self.aggs)
            self.aggs.append((e.n, 0, 0))
            return ("count", nid, e.op, ai, self.lower_span(e.of))
        if isinstance(e, ir.Quantile):
            nid = self._nid(
                "quantile",
                f"p{e.q_num}/{e.q_den} {e.op} {e.x_ms}ms (rank counts)")
            ai = len(self.aggs)
            self.aggs.append((e.q_num, e.q_den, e.x_ms))
            return ("q", nid, e.op, ai, self.lower_span(e.of))
        if isinstance(e, ir.TraceAnd):
            nid = self._nid("and")
            return ("and", nid, tuple(self.lower_trace(a) for a in e.args))
        if isinstance(e, ir.TraceOr):
            nid = self._nid("or")
            return ("or", nid, tuple(self.lower_trace(a) for a in e.args))
        if isinstance(e, ir.TraceNot):
            nid = self._nid("not")
            return ("not", nid, self.lower_trace(e.arg))
        raise StructuralCompileError(
            f"unknown trace node {type(e).__name__}")


def _assemble_terms(terms: list, blocks: list, cache_on=None,
                    staged_dicts: dict | None = None,
                    host_only: bool = False):
    """Per-block leaf term tables, one dictionary probe per DISTINCT
    dictionary (the compile_multi economics): [B, T] key ids,
    [B, T, R, 2] ranges, and — when a staged dictionary's device probe
    answered — [G, T, Vm] hit masks with the block -> group map.
    Reuses pipeline's probe internals so the host memmem path, the
    device packed-probe kernel, bit-packed masks, breaker fallback and
    watchdog bounds are all the SAME code the legacy terms run."""
    from . import packing
    from .multiblock import _dict_groups, block_bucket
    from .pipeline import _host_probe_tags

    import jax.numpy as jnp

    staged_dicts = staged_dicts or {}
    fp_of, rep_idx, rows_of = _dict_groups(blocks, cache_on=cache_on)
    T = len(terms)
    compiled: dict[bytes, tuple] = {}
    for fp, i in rep_idx.items():
        b = blocks[i]
        compiled[fp] = _probe_leaf_terms(
            b, terms, None if host_only else staged_dicts.get(fp),
            host_only=host_only)

    # the block axis in its bucket, as compile_multi's tables: rows
    # past the blocks stay key id -1 and no page or span names one
    B = block_bucket(len(blocks))
    rmax = 1
    for tk, tv, vr, vh in compiled.values():
        if vr is not None:
            rmax = max(rmax, vr.shape[1])
    R = _pow2(rmax)
    term_keys = np.full((B, T), -1, dtype=np.int32)
    val_ranges = np.tile(np.array([1, 0], dtype=np.int32), (B, T, R, 1))
    for fp, (tk, _tv, vr, _vh) in compiled.items():
        rows = np.asarray(rows_of[fp], dtype=np.int64)
        term_keys[rows[:, None], np.arange(T)] = tk
        r_n = vr.shape[1]
        val_ranges[rows[:, None, None], np.arange(T)[:, None],
                   np.arange(r_n)] = vr[:, :r_n]

    probe_fps = [fp for fp, c in compiled.items() if c[3] is not None]
    val_hits = block_group = None
    if probe_fps:
        hs = {fp: compiled[fp][3] for fp in probe_fps}
        if any(packing.is_packed_mask(h) for h in hs.values()):
            hs = {fp: packing.pack_mask_words(h) for fp, h in hs.items()}
        Vm = max(int(h.shape[1]) for h in hs.values())
        padded = [jnp.pad(hs[fp], ((0, 0), (0, Vm - hs[fp].shape[1])))
                  for fp in probe_fps]
        val_hits = jnp.stack(padded)                     # [G, T, Vm]
        block_group = np.full(B, -1, dtype=np.int32)
        for g, fp in enumerate(probe_fps):
            block_group[np.asarray(rows_of[fp], dtype=np.int64)] = g
    _ = _host_probe_tags  # referenced via _probe_leaf_terms
    return term_keys, val_ranges, val_hits, block_group


_LEAF_CACHE_MAX = 8
# one lock for every block's leaf-probe LRU (the _compile_cache_lock
# idiom): concurrent structural searches over one block must not race
# the OrderedDict get/move/evict protocol
_leaf_cache_lock = threading.Lock()


def _probe_leaf_terms(block, terms: list, staged_dict, host_only: bool):
    """One dictionary's leaf-term probe, memoized on the immutable
    container: (term_keys [T], term_vals, val_ranges [T,R,2], val_hits)
    — the exhaustive contract (missing key -> -1 row, empty value set
    -> empty ranges) because a structural leaf must evaluate False, not
    prune the block. Device products cache separately from host ones so
    the host route never touches a wedged device's arrays."""
    from .pipeline import (_device_probe_tags, _host_probe_tags,
                           NATIVE_SCAN_THRESHOLD)

    sig = (tuple(terms), bool(staged_dict is not None and not host_only))
    with _leaf_cache_lock:
        cache = getattr(block, "_structural_leaf_cache", None)
        if cache is None:
            cache = block._structural_leaf_cache = OrderedDict()
        hit = cache.get(sig)
        if hit is not None:
            cache.move_to_end(sig)
            return hit
    out = None
    if staged_dict is not None and not host_only:
        from tempo_tpu.robustness import BREAKER, GUARD, DeviceFault

        if not BREAKER.blocking():
            try:
                out = GUARD.run(
                    "dict_probe",
                    lambda: _device_probe_tags(
                        terms, block.key_dict, staged_dict,
                        exhaustive=True))
            except (ValueError, DeviceFault):
                out = None  # oversized needle / wedged probe: host path
    if out is None:
        from tempo_tpu.ops import native

        packed = (block.packed_val_dict()
                  if native.available()
                  and len(block.val_dict) >= NATIVE_SCAN_THRESHOLD
                  else None)
        out = _host_probe_tags(terms, block.key_dict, block.val_dict,
                               packed, True)
    with _leaf_cache_lock:
        cache[sig] = out
        while len(cache) > _LEAF_CACHE_MAX:
            cache.popitem(last=False)
    return out


# ---------------------------------------------------------------------------
# device lowering: the kernel-side mask (called INSIDE the jitted scan
# kernels; `plan` is static at every call site — the jit-purity lint's
# descriptor rule pins that, like the packed-residency `widths`)


def structural_entry_mask(kv_key, kv_val, entry_dur, entry_valid,
                          page_block, entry_dur_res, span_cols, tables,
                          *, plan, widths):
    """[P, E] bool trace verdicts for a compiled structural plan.
    Recursion over the STATIC plan runs at trace time and emits one
    fused computation — compiled, never interpreted per row. Span-level
    sub-plans evaluate to [S] masks over the padded span axis;
    aggregates reduce them to [P, E] through the per-entry span-range
    columns; trace-level leaves evaluate on the entry columns with the
    same unpack/membership code paths the legacy kernel uses. ``plan``
    (like the packed-residency ``widths``) is a static descriptor at
    every call site — the jit-purity lint's descriptor rule pins it."""
    import jax.numpy as jnp

    safe_pb = jnp.maximum(page_block, 0)
    valid = entry_valid & (page_block >= 0)[:, None]
    bucketed = plan[0] == "bucket"
    val_hits, block_group = tables[2], tables[3]
    bg_page = None
    if val_hits is not None and block_group is not None:
        bg_page = block_group[safe_pb]                   # [P]
    sctx = None
    if span_cols is not None:
        sctx = _span_ctx(span_cols, val_hits, block_group)
    ectx = (kv_key, kv_val, entry_dur, entry_dur_res, valid, safe_pb,
            bg_page)
    if bucketed:
        return _bucket_trace_mask(ectx, sctx, tables, widths,
                                  bucket=plan) & valid
    return _trace_mask(plan, ectx, sctx, tables, widths) & valid


def _span_ctx(span_cols, val_hits, block_group) -> tuple:
    """What the span-level evaluators read of a staged segment
    (_span_mask, _tile_leaf, _bucket_span_regs unpack it). Which
    block's tables a span row reads is said by the TILE, not by the
    row: staging starts every block's spans on a tile of the span axis
    (SPAN_TILE), so `tile_block` is S / SPAN_TILE indices, and where a
    hit mask rides so is the tiles' dictionary group."""
    import jax.numpy as jnp

    tile_block = jnp.maximum(span_cols["span_tile_block"], 0)
    bg_tile = None
    if val_hits is not None and block_group is not None:
        bg_tile = block_group[tile_block]                # [S / tile]
    return (span_cols["span_trace"] >= 0,                # s_valid
            tile_block,
            span_cols["span_parent"],
            span_cols["span_dur"],
            span_cols["span_kind"],
            span_cols["span_kv_key"],
            span_cols["span_kv_val"],
            span_cols["entry_span_begin"],
            span_cols["entry_span_count"],
            bg_tile,
            span_cols["span_last"])


def plan_joins(plan) -> tuple:
    """(rel, scans) of a launch of `plan`: `rel` is "desc" where the
    plan joins by ancestor (a bucket plan with relations runs that arm
    for every slot), else "child" where it joins by parent, else
    "none"; `scans` the running-max passes over the span axis its
    `desc` joins make, one a node (_descends)."""
    if not plan:
        return "none", 0
    if plan[0] == "bucket":
        n_desc = plan[1] if plan[3] else 0
        rel = "desc" if n_desc else "none"
    else:
        ops = list(_plan_ops(plan))
        n_desc = ops.count("desc")
        rel = "desc" if n_desc else "child" if "child" in ops else "none"
    return rel, n_desc


def _plan_ops(plan):
    """The ops of a plan's nodes: a node is a tuple that starts with its
    op, its children further tuples (and/or keep theirs in one)."""
    if isinstance(plan, tuple):
        if plan and isinstance(plan[0], str):
            yield plan[0]
        for sub in plan:
            yield from _plan_ops(sub)


def _descends(am, sm, s_last):
    """[S]: the spans of `sm` with a proper ancestor in `am`, as ONE
    running max over the span axis. Staging lays every trace out depth
    first and keeps each span's last descendant (stack_spans), so row
    `c` lies under row `a` iff a < c <= s_last[a]: `c` has an ancestor
    in `am` iff the largest `s_last` of the `am` rows BEFORE it reaches
    it. Rows of earlier traces (and blocks) end before `c`, so nothing
    leaks across them; no lookup has the span axis in its indices."""
    import jax
    import jax.numpy as jnp

    reach = jax.lax.cummax(jnp.where(am, s_last, -1))
    before = jnp.concatenate([jnp.full(1, -1, reach.dtype), reach[:-1]])
    return sm & (before >= jnp.arange(reach.shape[0], dtype=reach.dtype))


def _seg_count(m, seg_b, seg_n):
    """Matched spans per entry: exclusive cumsum + two gathers — a
    segment reduction with no scatter (the VPU lesson)."""
    import jax.numpy as jnp

    c = jnp.cumsum(m.astype(jnp.int32))
    exc = jnp.concatenate([jnp.zeros(1, jnp.int32), c])
    return exc[seg_b + seg_n] - exc[seg_b]


def _cmp_dev(a, b, op):
    if op == ">":
        return a > b
    if op == ">=":
        return a >= b
    if op == "<":
        return a < b
    if op == "<=":
        return a <= b
    if op == "==":
        return a == b
    return a != b


def _table_match(kk, vv, row_block, row_group, tables, i):
    """[N, M] bool: the entries with a kv slot that holds term `i`'s
    key and a value the term matches. `kk` / `vv` are [N, M, C] key and
    value ids, `row_block` [N] the block whose table row the N-th row
    of entries reads (a page of entries, a tile of spans): ONE lookup
    a row into `term_keys [B, T]` and `val_ranges [B, T, R, 2]`,
    broadcast over the row's M entries. Where a hit mask rides
    (`row_group` [N], the row's dictionary group or -1) membership is
    the mask's bit instead of the ranges'. `i` is static in a plan and
    traced in a slot program."""
    import jax.numpy as jnp

    from .packing import mask_select_grouped

    term_keys, val_ranges, val_hits = tables[:3]
    keym = kk == term_keys[row_block, i][:, None, None]
    lo = val_ranges[row_block, i, :, 0]                  # [N,R]
    hi = val_ranges[row_block, i, :, 1]
    v = vv[..., None]
    valm = ((v >= lo[:, None, None, :]) &
            (v <= hi[:, None, None, :])).any(-1)         # [N,M,C]
    if row_group is not None:
        safe_g = jnp.maximum(row_group, 0)
        safe_v = jnp.maximum(vv, 0).astype(jnp.int32)
        mh = (mask_select_grouped(val_hits, safe_g[:, None, None], i,
                                  safe_v)
              & (vv >= 0))
        valm = jnp.where((row_group >= 0)[:, None, None], mh, valm)
    return jnp.any(keym & valm, axis=-1)


def _tile_leaf(sctx, tables, i):
    """[S] bool: the spans tag term `i` matches, THE tag leaf over
    spans of a static plan (_span_mask) and of a slot program
    (_bucket_span_regs). The span axis folds to [S / tile, tile] (a
    reshape: every aligned tile is one block's, stack_spans' layout
    rule) and the tables are looked up by `span_tile_block`, S / tile
    indices a lookup at any B and R, where a lookup a row cost a v5e
    ~90 ms each at 8.4M rows."""
    s_valid, tile_block, s_kk, s_vv, bg_tile = (
        sctx[0], sctx[1], sctx[5], sctx[6], sctx[9])
    S, Cs = s_kk.shape
    n_tiles = tile_block.shape[0]
    m = _table_match(s_kk.reshape(n_tiles, -1, Cs),
                     s_vv.reshape(n_tiles, -1, Cs),
                     tile_block, bg_tile, tables, i)
    return m.reshape(S) & s_valid


def _span_mask(plan, sctx, tables, widths):
    """[S] bool mask for a span-level plan node. This is a DESCRIPTOR
    DISPATCHER over `plan` (branch structure decided at trace time):
    callers must pass the static plan, never traced data — the
    jit-purity lint's descriptor rule pins that contract."""
    import jax.numpy as jnp

    if plan is None:
        raise StructuralCompileError("span plan must not be None")
    (s_valid, _tile_block, s_par, s_dur, s_kind, _s_kk, _s_vv,
     _seg_b, _seg_n, _bg_tile, s_last) = sctx
    dur_params, kind_params = tables[4], tables[5]
    op = plan[0]
    if op == "tag":
        return _tile_leaf(sctx, tables, plan[2])
    if op == "dur":
        i = plan[2]
        return ((s_dur >= dur_params[i, 0]) &
                (s_dur <= dur_params[i, 1]) & s_valid)
    if op == "kind":
        i = plan[2]
        return (s_kind.astype(jnp.int32) == kind_params[i]) & s_valid
    if op == "and":
        m = _span_mask(plan[2][0], sctx, tables, widths)
        for sub in plan[2][1:]:
            m = m & _span_mask(sub, sctx, tables, widths)
        return m
    if op == "or":
        m = _span_mask(plan[2][0], sctx, tables, widths)
        for sub in plan[2][1:]:
            m = m | _span_mask(sub, sctx, tables, widths)
        return m
    if op == "not":
        return ~_span_mask(plan[2], sctx, tables, widths) & s_valid
    if op == "child":
        pm = _span_mask(plan[2], sctx, tables, widths)
        cm = _span_mask(plan[3], sctx, tables, widths)
        safe_par = jnp.maximum(s_par, 0)
        return cm & (s_par >= 0) & pm[safe_par]
    if op == "desc":
        am = _span_mask(plan[2], sctx, tables, widths)
        sm = _span_mask(plan[3], sctx, tables, widths)
        return _descends(am, sm, s_last)
    raise StructuralCompileError(f"bad span plan op {op!r}")


def _trace_mask(plan, ectx, sctx, tables, widths):
    """[P, E] bool mask for a trace-level plan node (plan/widths
    static; a span-less batch evaluates aggregates over zero counts).
    A descriptor dispatcher over `plan`, like _span_mask."""
    import jax.numpy as jnp

    from .packing import duration_ok, unpack_ids

    if plan is None:
        raise StructuralCompileError("trace plan must not be None")
    (kv_key, kv_val, entry_dur, entry_dur_res, valid, safe_pb,
     bg_page) = ectx
    dur_params, agg_params = tables[4], tables[6]
    kw, vw, dw = widths if widths is not None else (None, None, None)
    op = plan[0]
    if op == "ttag":
        i = plan[2]
        kk = unpack_ids(kv_key, kw)
        vv = unpack_ids(kv_val, vw)
        return _table_match(kk, vv, safe_pb, bg_page, tables, i) & valid
    if op == "tdur":
        i = plan[2]
        return duration_ok(entry_dur, entry_dur_res,
                           dur_params[i, 0], dur_params[i, 1], dw) & valid
    if op == "exists":
        if sctx is None:
            return jnp.zeros_like(valid)
        m = _span_mask(plan[2], sctx, tables, widths)
        return (_seg_count(m, sctx[7], sctx[8]) > 0) & valid
    if op == "count":
        cop, ai, sub = plan[2], plan[3], plan[4]
        if sctx is None:
            n = jnp.zeros(valid.shape, dtype=jnp.uint32)
        else:
            m = _span_mask(sub, sctx, tables, widths)
            n = _seg_count(m, sctx[7], sctx[8]).astype(jnp.uint32)
        return _cmp_dev(n, agg_params[ai, 0], cop) & valid
    if op == "q":
        qop, ai, sub = plan[2], plan[3], plan[4]
        if sctx is None:
            return jnp.zeros_like(valid)
        seg_b, seg_n = sctx[7], sctx[8]
        s_dur = sctx[3]
        m = _span_mask(sub, sctx, tables, widths)
        n = _seg_count(m, seg_b, seg_n).astype(jnp.uint32)
        qn = agg_params[ai, 0]
        qd = agg_params[ai, 1]
        x = agg_params[ai, 2]
        # nearest-rank r = ceil(q*n) in pure uint32 math — identical on
        # host (eval_host) so quantiles are bit-exact: no sort, no float
        r = (qn * n + qd - jnp.uint32(1)) // qd
        if qop in (">", ">="):
            inner = (s_dur > x) if qop == ">" else (s_dur >= x)
            ci = _seg_count(m & inner, seg_b, seg_n).astype(jnp.uint32)
            ok = ci >= n - r + jnp.uint32(1)
        elif qop in ("<", "<="):
            inner = (s_dur < x) if qop == "<" else (s_dur <= x)
            ci = _seg_count(m & inner, seg_b, seg_n).astype(jnp.uint32)
            ok = ci >= r
        else:  # == / != via the two one-sided rank tests
            chi = _seg_count(m & (s_dur >= x), seg_b,
                             seg_n).astype(jnp.uint32)
            clo = _seg_count(m & (s_dur <= x), seg_b,
                             seg_n).astype(jnp.uint32)
            eq = (chi >= n - r + jnp.uint32(1)) & (clo >= r)
            ok = eq if qop == "==" else ~eq
        return ok & (n > 0) & valid
    if op == "and":
        m = _trace_mask(plan[2][0], ectx, sctx, tables, widths)
        for sub in plan[2][1:]:
            m = m & _trace_mask(sub, ectx, sctx, tables, widths)
        return m
    if op == "or":
        m = _trace_mask(plan[2][0], ectx, sctx, tables, widths)
        for sub in plan[2][1:]:
            m = m | _trace_mask(sub, ectx, sctx, tables, widths)
        return m
    if op == "not":
        return ~_trace_mask(plan[2], ectx, sctx, tables, widths) & valid
    raise StructuralCompileError(f"bad trace plan op {op!r}")


def _cmp_dyn(a, b, opc):
    """Dynamic-opcode comparison (the bucket-program twin of _cmp_dev):
    all six verdicts compute, the traced compare code selects one."""
    import jax.numpy as jnp

    out = a != b
    for code, m in ((0, a > b), (1, a >= b), (2, a < b),
                    (3, a <= b), (4, a == b)):
        out = jnp.where(opc == code, m, out)
    return out


def _bucket_span_regs(sctx, core, n_slots, prog, has_rel) -> list:
    """Evaluate a span slot program: returns the register list (index 0
    = the dummy all-false register, register i+1 = slot i's [S] mask).
    Each slot computes every opcode arm from ITS dynamic row and
    selects by the traced opcode — the slot-machine dual of
    _span_mask's static descriptor dispatch. Pad slots (opcode 0)
    evaluate to false and are unreachable from any real slot."""
    import jax.numpy as jnp

    (s_valid, _tile_block, s_par, s_dur, s_kind, _s_kk, _s_vv,
     _seg_b, _seg_n, _bg_tile, s_last) = sctx
    term_keys, dur_params, kind_params = core[0], core[4], core[5]
    S = int(s_valid.shape[0])
    false = jnp.zeros(S, dtype=bool)
    safe_par = jnp.maximum(s_par, 0)
    regs = [false]
    for i in range(n_slots):
        opc, a, b = prog[i, 0], prog[i, 1], prog[i, 2]
        prev = jnp.stack(regs)                       # [i+1, S]
        ra = prev[jnp.clip(a, 0, i)]
        rb = prev[jnp.clip(b, 0, i)]
        val = false
        if term_keys is not None:
            val = jnp.where(opc == 1, _tile_leaf(sctx, core, a), val)
        if dur_params is not None:
            dur_m = ((s_dur >= dur_params[a, 0]) &
                     (s_dur <= dur_params[a, 1]) & s_valid)
            val = jnp.where(opc == 2, dur_m, val)
        if kind_params is not None:
            kind_m = ((s_kind.astype(jnp.int32) == kind_params[a])
                      & s_valid)
            val = jnp.where(opc == 3, kind_m, val)
        val = jnp.where(opc == 4, ra & rb, val)
        val = jnp.where(opc == 5, ra | rb, val)
        val = jnp.where(opc == 6, ~ra & s_valid, val)
        if has_rel:
            val = jnp.where(opc == 7,
                            rb & (s_par >= 0) & ra[safe_par], val)
            val = jnp.where(opc == 8, _descends(ra, rb, s_last), val)
        regs.append(val)
    return regs


def _bucket_trace_mask(ectx, sctx, tables, widths, *, bucket):
    """[P, E] bool verdicts for ONE query lane of a bucket-stacked
    group. ``bucket`` = ("bucket", NS, NT, has_rel) is the static
    descriptor (part of every consuming kernel's jit key, like
    ``plan``); tables[7]/tables[8] are this lane's span/trace slot
    programs. The result register is statically the last trace slot
    (the flattener's root-copy contract), so no dynamic final gather
    is needed."""
    import jax.numpy as jnp

    from .packing import duration_ok, unpack_ids

    core = tables[:7]
    span_prog, trace_prog = tables[7], tables[8]
    (kv_key, kv_val, entry_dur, entry_dur_res, valid, safe_pb,
     bg_page) = ectx
    term_keys, dur_params, agg_params = core[0], core[4], core[6]
    kw, vw, dw = widths if widths is not None else (None, None, None)
    NS, NT = bucket[1], bucket[2]
    sprev = seg_b = seg_n = s_dur = None
    if bucket[1]:
        if sctx is not None:
            sregs = _bucket_span_regs(sctx, core, NS, span_prog,
                                      bucket[3])
            sprev = jnp.stack(sregs)                 # [NS+1, S]
            seg_b, seg_n, s_dur = sctx[7], sctx[8], sctx[3]
    kk = vv = None
    if term_keys is not None:
        kk = unpack_ids(kv_key, kw)
        vv = unpack_ids(kv_val, vw)
    false = jnp.zeros(valid.shape, dtype=bool)
    tregs = [false]
    for i in range(NT):
        opc, a, b, c = (trace_prog[i, 0], trace_prog[i, 1],
                        trace_prog[i, 2], trace_prog[i, 3])
        prev = jnp.stack(tregs)
        ra = prev[jnp.clip(a, 0, i)]
        rb = prev[jnp.clip(b, 0, i)]
        val = false
        if term_keys is not None:
            ttag_m = _table_match(kk, vv, safe_pb, bg_page, core,
                                  a) & valid
            val = jnp.where(opc == 1, ttag_m, val)
        if dur_params is not None:
            tdur_m = duration_ok(entry_dur, entry_dur_res,
                                 dur_params[a, 0], dur_params[a, 1],
                                 dw) & valid
            val = jnp.where(opc == 2, tdur_m, val)
        if sprev is not None:
            sm = sprev[jnp.clip(a, 0, NS)]
            cnt = _seg_count(sm, seg_b, seg_n).astype(jnp.uint32)
            val = jnp.where(opc == 3, (cnt > 0) & valid, val)
            if agg_params is not None:
                count_m = _cmp_dyn(cnt, agg_params[b, 0], c) & valid
                val = jnp.where(opc == 4, count_m, val)
                qn = agg_params[b, 0]
                # pad agg rows are (0, 1, 0): the clamp keeps the
                # computed-but-unselected arm division-safe anyway
                qd = jnp.maximum(agg_params[b, 1], jnp.uint32(1))
                x = agg_params[b, 2]
                r = (qn * cnt + qd - jnp.uint32(1)) // qd
                hi_inner = jnp.where(c == 0, s_dur > x, s_dur >= x)
                lo_inner = jnp.where(c == 2, s_dur < x, s_dur <= x)
                c_hi = _seg_count(sm & hi_inner, seg_b,
                                  seg_n).astype(jnp.uint32)
                c_lo = _seg_count(sm & lo_inner, seg_b,
                                  seg_n).astype(jnp.uint32)
                ok_hi = c_hi >= cnt - r + jnp.uint32(1)
                ok_lo = c_lo >= r
                eq = ok_hi & ok_lo
                q_ok = jnp.where(c <= 1, ok_hi,
                                 jnp.where(c <= 3, ok_lo,
                                           jnp.where(c == 4, eq, ~eq)))
                val = jnp.where(opc == 5,
                                q_ok & (cnt > 0) & valid, val)
        elif agg_params is not None:
            # span-less batch: exists/q are false, count still compares
            # against zero — the _trace_mask sctx-None semantics
            n0 = jnp.zeros(valid.shape, dtype=jnp.uint32)
            count_m = _cmp_dyn(n0, agg_params[b, 0], c) & valid
            val = jnp.where(opc == 4, count_m, val)
        val = jnp.where(opc == 6, ra & rb, val)
        val = jnp.where(opc == 7, ra | rb, val)
        val = jnp.where(opc == 8, ~ra & valid, val)
        tregs.append(val)
    return tregs[-1]


# ---------------------------------------------------------------------------
# host reference evaluator (the differential-fuzz oracle and the
# live/WAL + proto-fallback execution path)


_CMP = {
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
}


def eval_host(expr: "ir.TraceExpr", sd) -> bool:
    """Reference semantics over a SearchData (with its span rows):
    byte-for-byte what the compiled kernels answer — substring tag
    terms, inclusive ranges, pointer joins, and the SAME integer
    rank-count quantile formula (never a sort, never a float). A
    malformed loop of parents is cut as staging cuts it
    (cut_parent_loops), so host and device agree on every input."""
    spans = list(getattr(sd, "spans", ()) or ())
    n_spans = len(spans)
    parents: list = []

    def parent_of() -> list:
        """Each span's parent in the trace or -1, loops cut; read by
        the joins alone, once a trace. A trace is tens of spans: a
        plain walk finds out whether there is a loop to cut at all,
        and only then is the rule's one helper asked."""
        if not parents and n_spans:
            raw = [sp.parent if 0 <= sp.parent < n_spans else -1
                   for sp in spans]
            if _has_parent_loop(raw):
                raw = cut_parent_loops(
                    np.array(raw, dtype=np.int32))[0].tolist()
            parents.extend(raw)
        return parents

    def sev(e) -> list:
        if isinstance(e, ir.SpanTag):
            out = []
            for sp in spans:
                vs = sp.kvs.get(e.key)
                out.append(bool(vs) and (not e.value or
                                         any(e.value in x for x in vs)))
            return out
        if isinstance(e, ir.SpanDur):
            return [e.lo_ms <= sp.dur_ms <= e.hi_ms for sp in spans]
        if isinstance(e, ir.SpanKind):
            return [sp.kind == e.kind for sp in spans]
        if isinstance(e, ir.SpanAnd):
            ms = [sev(a) for a in e.args]
            return [all(m[i] for m in ms) for i in range(n_spans)]
        if isinstance(e, ir.SpanOr):
            ms = [sev(a) for a in e.args]
            return [any(m[i] for m in ms) for i in range(n_spans)]
        if isinstance(e, ir.SpanNot):
            return [not v for v in sev(e.arg)]
        if isinstance(e, ir.ChildOf):
            pm, cm, par = sev(e.parent), sev(e.child), parent_of()
            return [cm[i] and par[i] >= 0 and pm[par[i]]
                    for i in range(n_spans)]
        if isinstance(e, ir.DescOf):
            am, sm, par = sev(e.anc), sev(e.span), parent_of()
            out = []
            for i in range(n_spans):
                ok = False
                if sm[i]:
                    p = par[i]      # no loops: the walk ends at a root
                    while p >= 0 and not ok:
                        ok, p = am[p], par[p]
                out.append(ok)
            return out
        raise StructuralCompileError(
            f"unknown span node {type(e).__name__}")

    def tev(e) -> bool:
        if isinstance(e, ir.TraceTag):
            vs = sd.kvs.get(e.key)
            return bool(vs) and (not e.value
                                 or any(e.value in x for x in vs))
        if isinstance(e, ir.TraceDur):
            return e.lo_ms <= sd.dur_ms <= e.hi_ms
        if isinstance(e, ir.Exists):
            return any(sev(e.of))
        if isinstance(e, ir.Count):
            return _CMP[e.op](sum(sev(e.of)), e.n)
        if isinstance(e, ir.Quantile):
            m = sev(e.of)
            n = sum(m)
            if n == 0:
                return False
            r = (e.q_num * n + e.q_den - 1) // e.q_den
            if e.op in (">", ">="):
                ci = sum(1 for i, v in enumerate(m) if v and
                         (spans[i].dur_ms > e.x_ms if e.op == ">"
                          else spans[i].dur_ms >= e.x_ms))
                return ci >= n - r + 1
            if e.op in ("<", "<="):
                ci = sum(1 for i, v in enumerate(m) if v and
                         (spans[i].dur_ms < e.x_ms if e.op == "<"
                          else spans[i].dur_ms <= e.x_ms))
                return ci >= r
            chi = sum(1 for i, v in enumerate(m)
                      if v and spans[i].dur_ms >= e.x_ms)
            clo = sum(1 for i, v in enumerate(m)
                      if v and spans[i].dur_ms <= e.x_ms)
            eq = (chi >= n - r + 1) and (clo >= r)
            return eq if e.op == "==" else not eq
        if isinstance(e, ir.TraceAnd):
            return all(tev(a) for a in e.args)
        if isinstance(e, ir.TraceOr):
            return any(tev(a) for a in e.args)
        if isinstance(e, ir.TraceNot):
            return not tev(e.arg)
        raise StructuralCompileError(
            f"unknown trace node {type(e).__name__}")

    return tev(expr)


# ---------------------------------------------------------------------------
# cost model + explain attribution


def plan_node_bytes(plan: tuple, n_spans: int, n_entries: int,
                    span_kv_slots: int = 1,
                    entry_kv_slots: int = 1) -> dict:
    """Per-node device-byte estimates — the unit the planner's
    calibrated scan rate (seconds/byte) turns into predicted seconds,
    and the conserved weights measured kernel time apportions over for
    the explain tree. Deliberately simple: bytes touched per op."""
    S = max(1, n_spans)
    PE = max(1, n_entries)
    out: dict[int, int] = {}

    def w_span(p) -> None:
        op, nid = p[0], p[1]
        if op == "tag":
            out[nid] = S * span_kv_slots * 8
        elif op == "dur":
            out[nid] = S * 4
        elif op == "kind":
            out[nid] = S
        elif op in ("and", "or"):
            out[nid] = S * len(p[2])
            for sub in p[2]:
                w_span(sub)
        elif op == "not":
            out[nid] = S
            w_span(p[2])
        elif op in ("child", "desc"):
            out[nid] = S * 12
            w_span(p[2])
            w_span(p[3])

    def w_trace(p) -> None:
        op, nid = p[0], p[1]
        if op == "ttag":
            out[nid] = PE * entry_kv_slots * 8
        elif op == "tdur":
            out[nid] = PE * 4
        elif op == "exists":
            out[nid] = S * 4 + PE * 8
            w_span(p[2])
        elif op in ("count", "q"):
            out[nid] = (S * 4 + PE * 8) * (2 if op == "q" else 1)
            w_span(p[4])
        elif op in ("and", "or"):
            out[nid] = PE * len(p[2])
            for sub in p[2]:
                w_trace(sub)
        elif op == "not":
            out[nid] = PE
            w_trace(p[2])

    w_trace(plan)
    return out


def span_device_bytes(span_cols) -> int:
    """Physical bytes of a staged span segment (budget accounting)."""
    if not span_cols:
        return 0
    return int(sum(int(getattr(a, "nbytes", 0))
                   for a in span_cols.values()))
