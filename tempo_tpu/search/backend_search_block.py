"""Backend search block: build, open, scan.

Role-equivalent to the reference's BackendSearchBlock
(tempodb/search/backend_search_block.go:28-298): at block completion the
WAL search entries are rewritten into the columnar container (`search`
object, page-compressed) plus a small JSON header (`search-header.json`)
used for block-level pruning without touching the container. Search =
header prune → dictionary query compile (may prune) → device kernel →
top-k rendered to TraceSearchMetadata.
"""

from __future__ import annotations

import json

from tempo_tpu import tempopb
from tempo_tpu.backend.raw import RawBackend
from tempo_tpu.backend.types import BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER
from tempo_tpu.encoding.v2.compression import compress, decompress

from .columnar import ColumnarPages, PageGeometry
from .data import SearchData
from .engine import ScanEngine, StagedPages, stage
from .pipeline import block_header_skip_reason, compile_query
from .results import SearchResults

_DEFAULT_ENGINE = None


def default_engine() -> ScanEngine:
    global _DEFAULT_ENGINE
    if _DEFAULT_ENGINE is None:
        _DEFAULT_ENGINE = ScanEngine()
    return _DEFAULT_ENGINE


def host_scan_single(pages: ColumnarPages, cq, top_k: int):
    """The single-block host fallback (breaker open, or the device
    dispatch faulted): the SAME scan_kernel pinned to the CPU backend
    over the host container — byte-identical to the device dispatch
    (same padded shapes, host range tables; equal start seconds resolve
    to the lowest flat index on both). The batched twin is
    search/batcher.host_scan."""
    import time

    import jax.numpy as jnp

    from tempo_tpu.observability import profile

    from .engine import (
        _bucket,
        cpu_pinned,
        fetch_scan_out,
        pad_page_axis,
        scan_kernel,
    )

    from .structural import STRUCTURAL

    t0 = time.perf_counter()
    with cpu_pinned():
        host = pad_page_axis(pages, _bucket(pages.n_pages))
        dev = {k: jnp.asarray(v) for k, v in host.items()}
        # structural predicate on the single-block host route: the
        # host-only compile attached range tables; span columns stage on
        # the CPU backend — same kernel, same plan, byte-identical
        st = getattr(cq, "structural", None)
        plan = s_tables = span_dev = None
        if st is not None:
            plan = st.plan
            s_tables = tuple(jnp.asarray(t) if t is not None else None
                             for t in st.tables())
            if STRUCTURAL.enabled:
                span_host = STRUCTURAL.stage_single(
                    pages, _bucket(pages.n_pages))
                if span_host is not None:
                    span_dev = {k: jnp.asarray(v)
                                for k, v in span_host.items()}
        out = scan_kernel(
            dev["kv_key"], dev["kv_val"], dev["entry_start"],
            dev["entry_end"], dev["entry_dur"], dev["entry_valid"],
            jnp.asarray(cq.term_keys), jnp.asarray(cq.val_ranges),
            jnp.uint32(cq.dur_lo), jnp.uint32(min(cq.dur_hi, 0xFFFFFFFF)),
            jnp.uint32(cq.win_start),
            jnp.uint32(min(cq.win_end, 0xFFFFFFFF)),
            None, None, span_dev, s_tables,
            n_terms=cq.n_terms, top_k=top_k, plan=plan)
        res = fetch_scan_out(out)
    profile.observe_stage("execute", "host_fallback",
                          time.perf_counter() - t0)
    return res


def write_search_block(backend: RawBackend, meta: BlockMeta,
                       entries: list[SearchData],
                       geometry: PageGeometry = PageGeometry(),
                       encoding: str | None = None) -> dict:
    # None = zstd when the codec exists on this host, else zlib — the
    # header records whichever codec actually wrote the pages, so reads
    # are unaffected. Production callers pass cfg.search_encoding.
    if encoding is None:
        from tempo_tpu.encoding.v2.compression import best_available

        encoding = best_available("zstd")
    pages = ColumnarPages.build(entries, geometry)
    blob = compress(pages.to_bytes(), encoding)
    header = dict(pages.header)
    header["encoding"] = encoding
    header["compressed_size"] = len(blob)
    if header.get("truncated_entries"):
        # surface kv-slot truncation (a silent false-negative class:
        # entries wider than C lose tags) — operators watch this counter
        from tempo_tpu.observability import metrics as obs

        obs.truncated_tag_entries.inc(header["truncated_entries"],
                                      tenant=meta.tenant_id)
    backend.write(meta.tenant_id, meta.block_id, NAME_SEARCH, blob)
    backend.write(meta.tenant_id, meta.block_id, NAME_SEARCH_HEADER,
                  json.dumps(header).encode())
    # record the container geometry on the block meta and re-commit it —
    # meta.json written last stays the commit record, now carrying what
    # the frontend job sharder needs (page count/bytes for range math)
    meta.search_pages = header["n_pages"]
    meta.search_size = len(blob)
    meta.search_entries_per_page = header["entries_per_page"]
    meta.search_kv_per_entry = header["kv_per_entry"]
    backend.write_block_meta(meta)
    return header


class BackendSearchBlock:
    def __init__(self, backend: RawBackend, meta: BlockMeta,
                 header: dict | None = None,
                 probe_min_vals: int | None = None):
        """header: an already-fetched rollup (TempoDB's header cache /
        restart snapshot) — saves one backend GET per container open.

        probe_min_vals: the device-probe staging threshold
        (cfg.search_device_probe_min_vals) — the single-block path must
        honor the same knob as the batcher, including <= 0 = host-only
        probing; None = the dict_probe library default."""
        self.backend = backend
        self.meta = meta
        self.probe_min_vals = probe_min_vals
        self._header: dict | None = header
        self._pages: ColumnarPages | None = None
        self._staged: StagedPages | None = None
        self._lock = __import__("threading").Lock()

    def header(self) -> dict:
        if self._header is None:
            self._header = json.loads(self.backend.read(
                self.meta.tenant_id, self.meta.block_id, NAME_SEARCH_HEADER
            ))
        return self._header

    def pages(self) -> ColumnarPages:
        """Load the host columnar container (cached). Device staging is a
        separate step: the batcher stages groups of blocks together, and
        dictionary-only readers (tag lookups) never need device arrays."""
        with self._lock:
            if self._pages is None:
                hdr = self.header()
                blob = self.backend.read(self.meta.tenant_id,
                                         self.meta.block_id, NAME_SEARCH)
                raw = decompress(blob, hdr.get("encoding", "zstd"))
                self._pages = ColumnarPages.from_bytes(raw)
            return self._pages

    def staged(self) -> StagedPages:
        """Device-stage this block alone (cached — HBM is the cache tier
        for hot blocks, cf. reference shouldCache heuristics). The batched
        serving path uses the batcher's group staging instead. The H2D
        transfer runs outside the lock shared with pages() so
        dictionary-only readers (tag lookups) never wait on it; a racing
        duplicate stage is benign and the first publish wins."""
        with self._lock:
            if self._staged is not None:
                return self._staged
        sp = stage(self.pages(), probe_min_vals=self.probe_min_vals)
        with self._lock:
            if self._staged is None:
                self._staged = sp
            return self._staged

    def search(self, req: tempopb.SearchRequest,
               results: SearchResults | None = None,
               engine: ScanEngine | None = None) -> SearchResults:
        from tempo_tpu.robustness import BREAKER, GUARD, DeviceFault

        from . import query_stats

        engine = engine or default_engine()
        results = results or SearchResults.for_request(req)
        results.metrics.inspected_blocks += 1
        qs = query_stats.current()

        reason = block_header_skip_reason(self.header(), req)
        if reason is not None:
            results.metrics.skipped_blocks += 1
            if qs is not None:
                qs.add_skip(reason)
            return results

        from tempo_tpu.ops import native
        from tempo_tpu.search.pipeline import NATIVE_SCAN_THRESHOLD

        def _packed(pages):
            return (pages.packed_val_dict()
                    if req.tags and native.available()
                    and len(pages.val_dict) >= NATIVE_SCAN_THRESHOLD
                    else None)

        out = render_pages = None
        pruned = False
        from tempo_tpu.observability import metrics as obs
        from tempo_tpu.search.ownership import OWNERSHIP

        # same contract as the batcher: breaker open/half-open without a
        # probe token means the host route — no staging put, no device
        # dispatch; a mid-flight DeviceFault falls through to host too.
        # Owner routing applies here exactly like the batched path: a
        # non-owner answers this block from the byte-identical host scan
        # instead of staging a duplicate device copy.
        allow_device = BREAKER.allow_device()
        if allow_device and OWNERSHIP.enabled:
            if not OWNERSHIP.owns_block(self.meta.block_id):
                allow_device = False
                obs.hbm_owner_routed.inc(route="non_owner_host")
        from tempo_tpu.search import structural as _structural

        expr = _structural.structural_query(req)
        if allow_device:
            try:
                sp = GUARD.run("h2d", self.staged)
                # staged_dict present → the substring probe runs on
                # device (staging already applied the size threshold);
                # the host memmem path stays the exact fallback for
                # oversized needles
                with query_stats.attributed_dispatch(qs,
                                                     fallback_wall=False):
                    # attributed: compilation can fire the device probe
                    cq = compile_query(
                        sp.pages.key_dict, sp.pages.val_dict, req,
                        packed_vals=_packed(sp.pages), cache_on=sp.pages,
                        staged_dict=sp.staged_dict)
                    if cq is not None and expr is not None:
                        from .pipeline import _dict_fingerprint

                        sd_map = None
                        if sp.staged_dict is not None:
                            fp = _dict_fingerprint(
                                sp.pages, sp.pages.key_dict,
                                sp.pages.val_dict)
                            sd_map = {fp: sp.staged_dict}
                        cq.structural = _structural.compile_structural(
                            expr, [sp.pages], cache_on=sp.pages,
                            staged_dicts=sd_map,
                            entry_kv_slots=sp.pages.geometry.kv_per_entry)
                        if qs is not None:
                            qs.add_structural(cq.structural)
                if cq is None:  # dictionary prefilter pruned the block
                    pruned = True
                else:
                    with query_stats.attributed_dispatch(qs):
                        out = engine.scan_staged(sp, cq)
                    obs.scan_dispatches.inc(mode="single", shards=1)
                    render_pages = sp.pages
                    placement = "device"
            except DeviceFault:
                out = None  # fault booked; byte-identical host path below
                pruned = False
        if out is None and not pruned:
            pages = self.pages()
            cq = compile_query(pages.key_dict, pages.val_dict, req,
                               packed_vals=_packed(pages), cache_on=pages,
                               host_only=True)
            if cq is not None and expr is not None:
                cq.structural = _structural.compile_structural(
                    expr, [pages], cache_on=pages, host_only=True,
                    entry_kv_slots=pages.geometry.kv_per_entry)
                if qs is not None:
                    qs.add_structural(cq.structural)
            if cq is None:
                pruned = True
            else:
                out = host_scan_single(pages, cq,
                                       engine._resolve_top_k(cq))
                obs.scan_dispatches.inc(mode="host_fallback", shards=1)
                render_pages = pages
                placement = "host"
        if pruned:
            results.metrics.skipped_blocks += 1
            if qs is not None:
                qs.add_skip("dict")
            return results

        count, inspected, scores, idx = out
        results.metrics.inspected_traces += inspected
        nbytes = int(self.header().get("compressed_size", 0))
        results.metrics.inspected_bytes += nbytes
        if qs is not None:
            qs.add_inspected(blocks=1, nbytes=nbytes, placement=placement)
        results.metrics.truncated_entries += int(
            self.header().get("truncated_entries", 0) or 0)
        holder = StagedPages(device={}, n_pages=render_pages.n_pages,
                             pages=render_pages)
        for m in engine.results(holder, cq, scores, idx):
            results.add(m)
        return results
