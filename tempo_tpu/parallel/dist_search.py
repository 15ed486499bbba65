"""Distributed scan: shard_map over the page axis + XLA collectives.

The TPU-native replacement for the reference's querier fan-out + Results
channel funnel (SURVEY.md §2.6): pages are sharded across the mesh's
"shards" axis, every device scans its local slice with the same predicate
kernel, then

  - match/inspected counts reduce with lax.psum (the Results counters),
  - per-shard top-k candidates all_gather and re-reduce to a global
    top-k (the frontend's result merge),

so one jit call returns the globally-merged answer on every device with
collectives riding ICI — no host round-trips per shard.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tempo_tpu.search.columnar import ColumnarPages
from tempo_tpu.search.engine import (
    DEVICE_ARRAYS,
    DEFAULT_TOP_K,
    book_topk,
    entry_match_mask,
    latest_k,
    masked_topk,
    pad_page_axis,
)
from tempo_tpu.search.pipeline import CompiledQuery
from .mesh import SCAN_AXIS


@dataclass
class ShardedPages:
    device: dict          # name -> jnp array sharded over the page axis
    n_pages: int          # real page count (pre-padding)
    pages: ColumnarPages  # host container
    # dict_probe.DeviceDict sharded over the VALUE axis when the block's
    # dictionary cleared the device-probe threshold (and, with the
    # offload planner enabled, its cost model — which charges the mesh
    # probe's all_gather/collective overhead) at staging time
    staged_dict: object = None
    # packed-residency width descriptor (search/packing.py): static per
    # staged block, part of the dist kernel's jit shape key
    widths: tuple | None = None
    # structural span columns (search/structural.py): REPLICATED by
    # default — the parent joins index the global span axis; the
    # structural verdict computes outside shard_map and enters the scan
    # page-sharded. With search_structural_shard_spans the segment
    # reshards trace-whole per page shard (span_sharded=True) and the
    # verdict evaluates INSIDE the shard over the local chunk.
    span_device: dict | None = None
    span_sharded: bool = False


class DistributedScanEngine:
    """Mesh-wide scan engine. API mirrors search.engine.ScanEngine but
    arrays live sharded across devices and the kernel runs under
    shard_map.

    `probe_min_vals`: the device-probe staging threshold, with
    cfg.search_device_probe_min_vals semantics everywhere: None = the
    dict_probe default (50k), <= 0 forces host-only. The PARAMETER
    default is 0 — constructing this engine without the knob keeps its
    historical never-stage-dictionaries behavior (the serving path's
    mesh batching lives in MultiBlockEngine, which has its own
    plumbing)."""

    def __init__(self, mesh: Mesh, top_k: int = DEFAULT_TOP_K,
                 probe_min_vals: int | None = 0):
        self.mesh = mesh
        self.top_k = top_k
        self.n_shards = mesh.devices.size
        self.probe_min_vals = probe_min_vals

    # ---- staging ----

    def stage(self, pages: ColumnarPages) -> ShardedPages:
        """Pad the page axis to a multiple of the shard count and place
        each array with a NamedSharding over the scan axis. Value
        dictionaries above the probe threshold stage value-axis-sharded
        for the mesh probe kernel (planner-vetoed like every other
        staging site — the decision accounts the all_gather cost via its
        n_shards input)."""
        import time

        from tempo_tpu.observability import profile
        from tempo_tpu.search.engine import stage_block_dict

        from tempo_tpu.search import packing

        n = self.n_shards
        B = -(-pages.n_pages // n) * n
        spec = NamedSharding(self.mesh, P(SCAN_AXIS))
        host = pad_page_axis(pages, B)
        widths = None
        if packing.PACKING.enabled:
            # packed residency: the sharded staging packs the same
            # per-column widths the single-block stage would choose
            widths = packing.PACKING.plan_widths(
                len(pages.key_dict), len(pages.val_dict),
                pages.max_dur_ms())
            if widths is not None:
                host = packing.pack_columns(host, widths)
        t0 = time.perf_counter()
        dev = {name: jax.device_put(arr, spec)
               for name, arr in host.items()}
        profile.observe_stage("h2d", "mesh", time.perf_counter() - t0,
                              nbytes=sum(int(v.nbytes)
                                         for v in host.values()))
        sd = stage_block_dict(pages, self.probe_min_vals,
                              n_shards=self.n_shards, mesh=self.mesh)
        from tempo_tpu.search.structural import STRUCTURAL

        span_dev = None
        span_sharded = False
        if STRUCTURAL.enabled:
            span_host = STRUCTURAL.stage_single(pages, B)
            if span_host is not None:
                if STRUCTURAL.shard_spans:
                    sh = STRUCTURAL.shard_span_segment(
                        span_host, self.n_shards, B,
                        pages.geometry.entries_per_page)
                    if sh is not None:
                        # segment-aligned sharding: every span array
                        # splits on its leading axis, aligned with the
                        # page sharding — per-shard span HBM ~1/P
                        span_dev = {k: jax.device_put(v, spec)
                                    for k, v in sh.items()}
                        span_sharded = True
                if span_dev is None:
                    # replicate (P()): parent pointers index the global
                    # span axis, which a page shard cannot see locally
                    rep = NamedSharding(self.mesh, P())
                    span_dev = {k: jax.device_put(v, rep)
                                for k, v in span_host.items()}
        return ShardedPages(device=dev, n_pages=pages.n_pages, pages=pages,
                            staged_dict=sd, widths=widths,
                            span_device=span_dev,
                            span_sharded=span_sharded)

    # ---- kernel ----

    @functools.partial(jax.jit, static_argnames=("self", "n_terms",
                                                 "top_k", "widths",
                                                 "plan", "span_sharded",
                                                 "shard_tail"))
    def _dist_kernel(self, kv_key, kv_val, entry_start, entry_end,
                     entry_dur, entry_valid, term_keys, val_ranges,
                     dur_lo, dur_hi, win_start, win_end, val_hits=None,
                     entry_dur_res=None, span_cols=None, s_tables=None,
                     *, n_terms: int, top_k: int, widths=None,
                     plan=None, span_sharded=False, shard_tail: int = 0):
        E = entry_valid.shape[1]
        local_flat = kv_key.shape[0] // self.n_shards * E
        pages_total = int(kv_key.shape[0])

        struct_mask = None
        sh_span_cols = sh_s_tables = None
        if plan is not None and not span_sharded:
            # structural verdicts evaluate over the REPLICATED span
            # columns outside shard_map (the parent joins index the
            # global span axis), then shard with the page axis below
            from tempo_tpu.search.structural import structural_entry_mask

            page_block = jnp.zeros(entry_valid.shape[0], dtype=jnp.int32)
            struct_mask = structural_entry_mask(
                kv_key, kv_val, entry_dur, entry_valid, page_block,
                entry_dur_res, span_cols, s_tables, plan=plan,
                widths=widths)
        elif plan is not None:
            # segment-aligned sharded spans: the chunk-local columns go
            # INTO the shard region and the joins stay shard-local
            sh_span_cols, sh_s_tables = span_cols, s_tables

        def shard_fn(kv_key, kv_val, entry_start, entry_end, entry_dur,
                     entry_valid, term_keys, val_ranges,
                     dur_lo, dur_hi, win_start, win_end, val_hits,
                     entry_dur_res, struct_mask, sh_span_cols,
                     sh_s_tables):
            if shard_tail:
                # remainder-shard ragged tail (static layout
                # descriptor, search_structural_remainder_pages): the
                # trailing pad pages live on the last shard(s); their
                # entries are already invalid, so this mask is
                # byte-identical — it records the layout in the jit key
                pp = entry_valid.shape[0]
                gpage = (jax.lax.axis_index(SCAN_AXIS).astype(jnp.int32)
                         * pp + jnp.arange(pp, dtype=jnp.int32))
                entry_valid = entry_valid & (
                    gpage < jnp.int32(pages_total - shard_tail))[:, None]
            mask = entry_match_mask(
                kv_key, kv_val, entry_start, entry_end, entry_dur,
                entry_valid, term_keys, val_ranges, dur_lo, dur_hi,
                win_start, win_end, n_terms=n_terms, val_hits=val_hits,
                entry_dur_res=entry_dur_res, widths=widths,
            )
            if struct_mask is not None:
                mask = mask & struct_mask
            if plan is not None and span_sharded:
                from tempo_tpu.search.structural import \
                    structural_entry_mask

                page_block = jnp.zeros(entry_valid.shape[0],
                                       dtype=jnp.int32)
                mask = mask & structural_entry_mask(
                    kv_key, kv_val, entry_dur, entry_valid, page_block,
                    entry_dur_res, sh_span_cols, sh_s_tables, plan=plan,
                    widths=widths)
            local_count = jnp.sum(mask, dtype=jnp.int32)
            local_inspected = jnp.sum(entry_valid, dtype=jnp.int32)
            scores, idx = masked_topk(mask, entry_start, top_k)
            # localize → globalize flat indices
            shard = jax.lax.axis_index(SCAN_AXIS).astype(jnp.int32)
            gidx = idx + shard * local_flat
            # reduce across the mesh: counts psum, candidates all_gather
            count = jax.lax.psum(local_count, SCAN_AXIS)
            inspected = jax.lax.psum(local_inspected, SCAN_AXIS)
            all_scores = jax.lax.all_gather(scores, SCAN_AXIS).reshape(-1)
            all_idx = jax.lax.all_gather(gidx, SCAN_AXIS).reshape(-1)
            top_scores, top_idx = latest_k(
                all_scores, all_idx, min(top_k, all_scores.shape[0]))
            return count, inspected, top_scores, top_idx

        from tempo_tpu.parallel.mesh import shard_map_compat

        return shard_map_compat(
            shard_fn, mesh=self.mesh,
            # val_hits (the device-probe hit mask) replicates like the
            # other predicate tables; a None leaf makes its spec a no-op;
            # the packed-duration residual shards with the page axis.
            # Sharded span columns split on their leading axis (chunk-
            # per-shard span axis / page axis); structural parameter
            # tables replicate.
            in_specs=(P(SCAN_AXIS), P(SCAN_AXIS), P(SCAN_AXIS), P(SCAN_AXIS),
                      P(SCAN_AXIS), P(SCAN_AXIS),
                      P(), P(), P(), P(), P(), P(), P(), P(SCAN_AXIS),
                      P(SCAN_AXIS), P(SCAN_AXIS), P()),
            out_specs=(P(), P(), P(), P()),
            # all_gather+top_k yields identical values on every shard, but
            # the replication checker can't infer it through the gather
            check=False,
        )(kv_key, kv_val, entry_start, entry_end, entry_dur, entry_valid,
          term_keys, val_ranges, dur_lo, dur_hi, win_start, win_end,
          val_hits, entry_dur_res, struct_mask, sh_span_cols,
          sh_s_tables)

    # ---- public API ----

    def scan_staged(self, sp: ShardedPages, cq: CompiledQuery):
        from tempo_tpu.observability import profile
        from tempo_tpu.search import query_stats

        # attributed: a query running through the distributed engine
        # bills its mesh dispatch (stages incl. lock_wait) to the
        # active QueryStats — same contract as the batched paths
        with query_stats.attributed_dispatch(), \
                profile.dispatch("mesh") as rec:
            d = sp.device
            k = self.top_k
            while k < cq.limit:
                k *= 2
            from tempo_tpu.search.engine import ScanEngine

            st = getattr(cq, "structural", None)
            with rec.stage("build"):
                # every replicated operand resident on the mesh before
                # the collective lock (parallel.mesh.put_replicated)
                tk, vr, dlo, dhi, ws, we = ScanEngine.query_device_params(
                    cq, self.mesh)
                vh = getattr(cq, "val_hits", None)
                if vh is not None:
                    from tempo_tpu.parallel.mesh import put_replicated

                    vh = put_replicated(self.mesh, vh)
                s_tables = None if st is None else st.device_tables(
                    self.mesh)
            widths = getattr(sp, "widths", None)
            plan = None if st is None else st.plan
            span_cols = (getattr(sp, "span_device", None)
                         if st is not None else None)
            span_sharded = bool(st is not None
                                and getattr(sp, "span_sharded", False))
            from tempo_tpu.search.structural import STRUCTURAL

            # this engine's staging always pads minimally, but the
            # ragged-tail descriptor only enters the jit key under the
            # remainder-shard gate (off = the historical key exactly)
            shard_tail = 0
            if STRUCTURAL.remainder_pages:
                shard_tail = int(d["kv_key"].shape[0]) - int(sp.n_pages)
            miss = rec.compile_check(
                ("dist", d["kv_key"].shape, str(d["kv_key"].dtype),
                 str(d["kv_val"].dtype), vr.shape,
                 None if vh is None else (tuple(vh.shape), str(vh.dtype)),
                 widths, cq.n_terms, k,
                 None if st is None else st.shape_sig(), span_sharded,
                 shard_tail))
            from tempo_tpu.parallel.mesh import locked_collective

            # process-wide collective-ordering lock (parallel.mesh):
            # shared with the multiblock engine and the dictionary probe,
            # so no two threads can interleave per-device shard_map
            # queues; time queued behind others lands in lock_wait
            stage = "compile" if miss else "execute"
            book_topk(rec, d["entry_valid"].size // self.n_shards, k)
            with locked_collective(rec):
                with rec.stage(stage):
                    out = self._dist_kernel(
                        d["kv_key"], d["kv_val"],
                        d["entry_start"], d["entry_end"], d["entry_dur"],
                        d["entry_valid"],
                        tk, vr, dlo, dhi, ws, we, vh,
                        d.get("entry_dur_res"), span_cols, s_tables,
                        n_terms=cq.n_terms, top_k=k, widths=widths,
                        plan=plan, span_sharded=span_sharded,
                        shard_tail=shard_tail,
                    )
            # fence after releasing the collective lock: a fenced wait
            # under dispatch_lock would stall every other mesh dispatch
            # behind this kernel (lock-order suite); the stage timer
            # accumulates so kernel time still books to compile/execute
            with rec.stage(stage):
                rec.fence(out)
            from tempo_tpu.search.engine import fetch_scan_out

            with rec.stage("d2h"):
                res = fetch_scan_out(out)
            rec.add_bytes(d2h=res[2].nbytes + res[3].nbytes + 8)
            # scan_bytes: the planner's per-byte scan-rate feed (physical
            # staged bytes this dispatch read — packed when packing is on)
            rec.set(n_pages=sp.n_pages, shards=self.n_shards,
                    scan_bytes=sum(int(a.nbytes) for a in d.values()))
        return res

    def scan(self, pages: ColumnarPages, cq: CompiledQuery):
        return self.scan_staged(self.stage(pages), cq)

    def results(self, sp: ShardedPages, cq: CompiledQuery,
                scores: np.ndarray, idx: np.ndarray) -> list:
        from tempo_tpu.search.engine import ScanEngine

        helper = ScanEngine(self.top_k)
        # ShardedPages and StagedPages share the fields results() needs
        return helper.results(sp, cq, scores, idx)
