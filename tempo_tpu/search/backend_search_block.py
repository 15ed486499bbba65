"""Backend search block: build, open.

Role-equivalent to the write and load halves of the reference's
BackendSearchBlock (tempodb/search/backend_search_block.go:28-298): at
block completion the WAL search entries are rewritten into the columnar
container (`search` object, page-compressed) plus a small JSON header
(`search-header.json`) used for block-level pruning without touching
the container. The search itself — header prune → dictionary query
compile (may prune) → device kernel → top-k rendered to
TraceSearchMetadata — is the batcher's (search/batcher.py), over the
block's ScanJob: one block is a one-block batch.
"""

from __future__ import annotations

import json

from tempo_tpu.backend.raw import RawBackend
from tempo_tpu.backend.types import BlockMeta, NAME_SEARCH, NAME_SEARCH_HEADER
from tempo_tpu.encoding.v2.compression import compress, decompress

from .columnar import ColumnarPages, PageGeometry
from .data import SearchData


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
                 header: dict | None = None):
        """header: an already-fetched rollup (TempoDB's header cache /
        restart snapshot) — saves one backend GET per container open."""
        self.backend = backend
        self.meta = meta
        self._header: dict | None = header
        self._pages: ColumnarPages | None = None
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

    def scan_job(self):
        """The whole container as one batcher job — what a search of
        this block alone hands BlockBatcher.search (a page-range job is
        TempoDB._scan_job's)."""
        from .batcher import ScanJob

        hdr = self.header()
        return ScanJob(
            key=(self.meta.block_id, 0, hdr["n_pages"]), pages_fn=self.pages,
            header=hdr, n_pages=hdr["n_pages"], n_entries=hdr["n_entries"],
            geometry=(hdr["entries_per_page"], hdr["kv_per_entry"]),
            meta=self.meta)
