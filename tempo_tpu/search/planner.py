"""Adaptive host/device offload planner: the profiler turned into policy.

PR 4 proved the host/device crossover is real — at 10M distinct values
the dictionary probe wins on chip but loses 2x on CPU — yet the only
policy was the static ``search_device_probe_min_vals`` threshold. PR 5
built the per-stage dispatch profiler as the measurement substrate. This
module closes the loop: a per-decision cost model over the LIVE profiler
observations chooses, per block group at plan time, whether the
dictionary substring prefilter runs on host (memmem / numpy scan folded
to id ranges) or on device (packed-dictionary rolling-window kernel) —
the central question of "To GPU or Not to GPU" (arxiv 2605.15957) and
the offloading OLAP engine (arxiv 2601.19911): pick placement from a
learned model, not a hand-tuned constant.

Cost model (all inputs observable, nothing guessed twice):

  host(T, B)   = T · rate(host_probe, T·B) · B
  device(...)  = T · rate(device_probe, T·S) · S        probe kernel
               + [pack(B) + h2d(S)]  if not HBM-resident  staging
               + fixed(dispatch)                          launch overhead
               + fixed(compile)      if the jit shape signature is
                                     UNSEEN in the profiler's set
               + fixed(collective)   if mesh-sharded (the all_gather +
                                     dispatch-lock cost of the mesh probe)

where B = real dictionary bytes, S = staged (padded buf+pos+off) bytes,
T = term count. Rates are EWMAs over recent observations, bucketed by
log-size so the model tracks the measured non-linearity (the CPU probe
is ~linear at 1M values and super-linear at 10M, CPU timings); fixed
costs are plain EWMAs. Observations arrive two ways:

  - the planner registers as a dispatch-profiler listener
    (observability/profile.py): every finished ``dict_probe`` dispatch
    record feeds the device-probe rate / compile / collective costs,
    and every ``dict_probe`` h2d staging observation feeds the h2d rate;
  - the host prefilter (pipeline._probe_tags) reports its wall time +
    scanned bytes directly (it needs to attach the dictionary
    fingerprint for predicted-vs-actual tracking).

Cold processes don't guess: the first decision runs a one-shot
microbenchmark (a ~100 KB synthetic dictionary through both paths) so
the seed rates are THIS host's, not a constant — a CPU-only process
seeds a slow device-probe rate and correctly keeps 720 MB dictionaries
on host instead of staging them blindly.

Override semantics (the static threshold remains the floor):

  - planner disabled (``search_offload_planner_enabled: false``, the
    default): behavior-identical to the static-threshold path — call
    sites never consult the planner;
  - ``search_device_probe_min_vals <= 0``: host-only, planner or not
    (call sites never reach the planner below the floor);
  - dictionaries >= the threshold: the planner chooses; its "host"
    verdict vetoes staging/probing that the static path would have done.

Both paths are exact (the probe is a prefilter, the scan kernels accept
either product), so planner decisions can never change results — only
where the time goes. Decisions + predicted-vs-actual error are exported
at /debug/planner, ``tempo_search_offload_decisions_total`` /
``tempo_search_offload_predict_error_ratio``, and replayable offline
from a /debug/profile dump via scripts/calibrate_offload.py.
"""

from __future__ import annotations

import threading
from collections import OrderedDict, deque
from dataclasses import dataclass, field

from tempo_tpu.observability import metrics as obs

# per-byte cost kinds (seconds per byte; probe kinds are per TERM-byte —
# observations pass nbytes = n_terms * bytes so predictions and
# observations stay in one unit). "scan" is the fused scan kernel's
# execute rate over PHYSICAL staged bytes: with packed residency the
# same pages land in smaller buckets, so the rate table is effectively
# bucketed by the columns' packed width — the /debug/planner view an
# operator reads to see what a byte of residency buys.
PER_BYTE_KINDS = ("host_probe", "device_probe", "pack", "h2d", "scan",
                  # ingest-side analytics reduction (search/analytics
                  # .py): seconds per summary-row byte — observational
                  # like "scan" (fills from live consume_blob calls)
                  "analytics")
# kinds the one-shot microbenchmark seeds: everything the probe
# DECISION consumes. "scan" is observational (it needs a real staged
# batch, which the seed deliberately never creates) and fills from the
# first live dispatches instead.
SEEDED_KINDS = ("host_probe", "device_probe", "pack", "h2d")
# fixed per-event costs (seconds)
FIXED_KINDS = ("dispatch", "compile", "collective")

# conservative cold-start rates used only when the microbenchmark seed is
# disabled or failed — roughly a shared-CPU host, which biases toward
# host (the safe side: never stage hundreds of MB on a guess)
_DEFAULT_RATES = {
    "host_probe": 4e-9,      # ~250 MB/s substring scan
    "device_probe": 8e-9,    # ~125 MB/s (CPU-backend probe kernel)
    "pack": 6e-9,
    "h2d": 1e-9,             # ~1 GB/s put
    "scan": 1e-10,           # ~10 GB/s linear pass (HBM-bound on chip)
    "analytics": 2e-9,       # ~500 MB/s batched summary-row reduction
}
_DEFAULT_FIXED = {"dispatch": 1e-3, "compile": 0.5, "collective": 2e-3}

# staged-bytes estimate when the packed layout doesn't exist yet: buf u8
# (1x) + pos i32 (4x) over pow2-padded byte axis (~1.5x average waste);
# off/n_real are noise at probe scale
_STAGED_FACTOR = 7.5

_SEED_VALS = 2048  # microbenchmark dictionary size (small: the seed must
# cost one small compile + a few ms, not a real staging)


def dict_bytes_est(val_dict, cache_on=None) -> int:
    """Estimated utf-8 byte length of a value dictionary, from an evenly
    spaced 256-value sample — O(1)-ish where an exact sum is O(dict),
    memoized on the immutable container when one is given."""
    if cache_on is not None:
        hit = getattr(cache_on, "_dict_nbytes_est", None)
        if hit is not None:
            return hit
    n = len(val_dict)
    if n == 0:
        est = 0
    elif n <= 256:
        est = sum(len(v.encode("utf-8")) for v in val_dict)
    else:
        step = n // 256
        sample = val_dict[::step][:256]
        est = int(sum(len(v.encode("utf-8")) for v in sample)
                  / len(sample) * n)
    if cache_on is not None:
        cache_on._dict_nbytes_est = est
    return est


@dataclass
class Decision:
    """One planner verdict, kept in the decision ring until its actual
    cost arrives (predicted-vs-actual is the calibration signal)."""
    seq: int
    site: str                 # "stage" | "compile" | "offline"
    target: str               # "host" | "device"
    fp: str | None            # dictionary fingerprint (hex prefix)
    inputs: dict
    predicted_host_s: float
    predicted_device_s: float
    # the chosen side's PROBE-ONLY component (what the later observation
    # actually measures — staging/fixed costs are observed separately)
    predicted_probe_s: float
    # compile cost charged into predicted_device_s when the model
    # predicted a jit miss — a compile-stage dispatch record measures
    # trace+compile+run in one wall time, so resolution against such a
    # record must compare predicted_probe_s + this, not probe alone
    predicted_compile_s: float = 0.0
    actual_s: float | None = None
    error: float | None = None

    def as_dict(self) -> dict:
        d = {
            "seq": self.seq, "site": self.site, "target": self.target,
            "inputs": self.inputs,
            "predicted_host_ms": round(self.predicted_host_s * 1e3, 3),
            "predicted_device_ms": round(self.predicted_device_s * 1e3, 3),
            "predicted_probe_ms": round(self.predicted_probe_s * 1e3, 3),
        }
        if self.fp:
            d["fp"] = self.fp
        if self.actual_s is not None:
            d["actual_probe_ms"] = round(self.actual_s * 1e3, 3)
            d["abs_rel_error"] = round(self.error, 3)
        return d


class _Ewma:
    __slots__ = ("value", "n")

    def __init__(self):
        self.value = None
        self.n = 0

    def update(self, x: float, alpha: float) -> None:
        self.value = x if self.value is None else \
            alpha * x + (1 - alpha) * self.value
        self.n += 1


def _bucket(nbytes: int) -> int:
    """4x-wide log-size buckets: rates are size-dependent (pow2 padding,
    cache effects, the measured super-linear CPU probe at 10M values)."""
    return max(0, int(nbytes).bit_length() // 2)


class OffloadPlanner:
    """Process-wide planner (module singleton ``PLANNER``, the REGISTRY /
    PROFILER idiom): TempoDBConfig flips ``enabled``; the staging and
    query-compile sites consult ``decide_probe`` only when the static
    threshold would have chosen the device path."""

    def __init__(self, enabled: bool = False, alpha: float = 0.25,
                 ring_size: int = 256, seed: bool = True):
        self.enabled = enabled
        self.alpha = alpha
        self.seed_on_first_use = seed
        self._lock = threading.Lock()
        self._rates: dict[tuple, _Ewma] = {}       # (kind, bucket)
        self._rates_global: dict[str, _Ewma] = {k: _Ewma()
                                                for k in PER_BYTE_KINDS}
        self._fixed: dict[str, _Ewma] = {k: _Ewma() for k in FIXED_KINDS}
        self._ring: deque = deque(maxlen=ring_size)
        self._seq = 0
        self._seeded = False
        self._seeding = False        # gates the profiler listeners so the
        # seed microbenchmark's own dispatch doesn't double-feed the model
        self._seed_ms = None
        # True once a REAL device probe has been observed (observe(), not
        # the seed's direct _update) — stage-time decisions, which have no
        # exact jit signature, predict a compile until then
        self._probe_observed = False
        self._decisions = {"host": 0, "device": 0}
        self._mispredict = _Ewma()   # EWMA of |pred-actual|/actual

    # ------------------------------------------------------------------
    # cost model

    def rate(self, kind: str, nbytes: int) -> float:
        """Seconds per byte for `kind` at this size: exact bucket →
        nearest observed bucket → global EWMA → seed default."""
        b = _bucket(nbytes)
        with self._lock:
            e = self._rates.get((kind, b))
            if e is not None and e.value is not None:
                return e.value
            near = None
            for (k, kb), ev in self._rates.items():
                if k != kind or ev.value is None:
                    continue
                if near is None or abs(kb - b) < abs(near[0] - b):
                    near = (kb, ev.value)
            if near is not None:
                return near[1]
            g = self._rates_global[kind]
            if g.value is not None:
                return g.value
        return _DEFAULT_RATES[kind]

    def fixed(self, kind: str) -> float:
        with self._lock:
            e = self._fixed[kind]
            if e.value is not None:
                return e.value
        return _DEFAULT_FIXED[kind]

    def observe(self, kind: str, seconds: float, nbytes: int = 0,
                fp: bytes | str | None = None) -> None:
        """Feed one measurement. Per-byte kinds need nbytes (probe kinds:
        n_terms * bytes); fixed kinds ignore it. `fp` (dictionary
        fingerprint) resolves the pending decision's predicted-vs-actual
        error. Noop when the planner is disabled — call sites on hot
        paths must stay free when the feature is off — and while the
        seed microbenchmark runs: its pack/probe go through the real
        dict_probe code whose instrumentation (pack_device_dict's pack
        observation, the profiler listeners) would double-feed the EWMAs
        on top of the seed's own direct updates."""
        if not self.enabled or self._seeding:
            return
        if kind == "device_probe":
            self._probe_observed = True
        self._update(kind, seconds, nbytes)
        if kind in ("host_probe", "device_probe"):
            self._resolve(kind, seconds, fp)

    def _update(self, kind: str, seconds: float, nbytes: int) -> None:
        with self._lock:
            if kind in FIXED_KINDS:
                self._fixed[kind].update(seconds, self.alpha)
                return
            if nbytes <= 0:
                return
            r = seconds / nbytes
            key = (kind, _bucket(nbytes))
            e = self._rates.get(key)
            if e is None:
                e = self._rates[key] = _Ewma()
            e.update(r, self.alpha)
            self._rates_global[kind].update(r, self.alpha)

    def _resolve(self, kind: str, seconds: float,
                 fp: bytes | str | None,
                 include_compile: bool = False) -> None:
        """Match an observed probe run to the newest unresolved decision
        for the same dictionary+side; record the relative error.
        `include_compile`: the observation came from a compile-stage
        dispatch record (trace+compile+run in one wall time), so compare
        against the decision's predicted compile cost too — otherwise a
        correctly predicted cold-shape compile books as ~100% error."""
        target = "host" if kind == "host_probe" else "device"
        fph = self._fp_hex(fp)
        err = None
        with self._lock:
            for d in reversed(self._ring):
                if d.actual_s is not None or d.target != target:
                    continue
                if fph is not None and d.fp is not None and d.fp != fph:
                    continue
                d.actual_s = seconds
                predicted = d.predicted_probe_s
                if include_compile:
                    predicted += d.predicted_compile_s
                base = max(seconds, 1e-9)
                err = d.error = abs(predicted - seconds) / base
                self._mispredict.update(err, self.alpha)
                break
        if err is not None:
            obs.offload_predict_error.observe(err)

    @staticmethod
    def _fp_hex(fp) -> str | None:
        if not fp:
            return None
        return fp[:16] if isinstance(fp, str) else fp.hex()[:16]

    # ------------------------------------------------------------------
    # decisions

    def decide_probe(self, *, n_vals: int, dict_bytes: int,
                     n_terms: int = 1, resident: bool = False,
                     packed: bool = False, staged_bytes: int | None = None,
                     n_shards: int = 1, shape_key=None,
                     fp: bytes | str | None = None,
                     site: str = "compile") -> Decision:
        """Host or device for one dictionary's substring prefilter. Call
        sites consult this ONLY above the static threshold floor (and
        never when ``search_device_probe_min_vals <= 0`` — host is forced
        there before the planner is reached).

        `resident`: staged device arrays already in HBM (compile-time
        decisions over a staged batch); `packed`: the host-side packing
        exists (an evicted batch re-stages without re-packing);
        `shape_key`: the probe kernel's jit signature, checked against
        the profiler's shape-signature set to predict a compile;
        `n_shards` > 1 adds the mesh collective cost (the all_gather in
        dist_probe_kernel + the process-wide dispatch lock)."""
        self._ensure_seeded()
        T = max(1, int(n_terms))
        B = max(1, int(dict_bytes))
        S = int(staged_bytes) if staged_bytes else int(B * _STAGED_FACTOR)

        host_s = self.rate("host_probe", T * B) * T * B

        dev_probe_s = self.rate("device_probe", T * S) * T * S
        dev_s = dev_probe_s + self.fixed("dispatch")
        if not resident:
            dev_s += self.rate("h2d", S) * S
            if not packed:
                dev_s += self.rate("pack", B) * B
        if n_shards > 1:
            dev_s += self.fixed("collective")
        if shape_key is not None:
            from tempo_tpu.observability.profile import PROFILER

            jit_miss = not PROFILER.seen(shape_key)
        else:
            # stage-time decisions have no exact signature yet: assume a
            # compile until a real device probe has run in this process
            # (the seed feeds rates via _update, deliberately NOT this
            # flag — a cold process's first big dictionary WILL pay the
            # first-shape XLA compile and the prediction must charge it)
            jit_miss = not self._probe_observed
        compile_s = self.fixed("compile") if jit_miss else 0.0
        dev_s += compile_s

        target = "device" if dev_s < host_s else "host"
        with self._lock:
            self._seq += 1
            d = Decision(
                seq=self._seq, site=site, target=target,
                fp=self._fp_hex(fp),
                inputs={"n_vals": int(n_vals), "dict_bytes": B,
                        "n_terms": T, "resident": bool(resident),
                        "staged_bytes": S, "n_shards": int(n_shards),
                        "jit_miss": bool(jit_miss)},
                predicted_host_s=host_s, predicted_device_s=dev_s,
                predicted_probe_s=(dev_probe_s if target == "device"
                                   else host_s),
                predicted_compile_s=(compile_s if target == "device"
                                     else 0.0),
            )
            self._ring.append(d)
            self._decisions[target] += 1
        obs.offload_decisions.inc(target=target, site=site)
        from . import query_stats

        qs = query_stats.current()
        if qs is not None:
            # the query this decision was made FOR sees it in its own
            # explain: target + the chosen side's predicted cost
            qs.add_planner(target, d.predicted_device_s
                           if target == "device" else d.predicted_host_s)
        return d

    # ------------------------------------------------------------------
    # seeding

    def _ensure_seeded(self) -> None:
        if self._seeded or not self.seed_on_first_use:
            return
        with self._lock:
            if self._seeded:
                return
            self._seeded = True   # set FIRST so the seed can't recurse
            self._seeding = True  # gate the profiler listeners: the
            # seed's own probe dispatch emits a dict_probe record +
            # h2d staging observation, and booking those ON TOP of the
            # seed's direct _update calls would double-feed the EWMAs
            # with contradictory samples (full compile wall vs warm/2)
        import time

        t0 = time.perf_counter()
        try:
            self._seed()
        except Exception:  # noqa: BLE001 — seeding is best-effort; the
            pass           # default rates keep decisions sane
        finally:
            self._seeding = False
        self._seed_ms = round((time.perf_counter() - t0) * 1e3, 1)

    def _seed(self) -> None:
        """One-shot microbenchmark: run a small synthetic dictionary
        through both probe paths so a cold process decides from THIS
        host's measured rates (a CPU-only backend seeds a slow device
        rate; a real accelerator seeds a fast one) instead of constants.
        Costs a few ms plus one small XLA compile."""
        import time

        import numpy as np

        vals = [f"seed-value-{i:07d}" for i in range(_SEED_VALS)]
        nb = sum(len(v) for v in vals)
        arr = np.array(vals, dtype=np.str_)
        t0 = time.perf_counter()
        np.char.find(arr, "seed-value-0000512")
        self._update("host_probe", time.perf_counter() - t0, nb)

        from . import dict_probe

        t0 = time.perf_counter()
        pd = dict_probe.pack_device_dict(vals)
        self._update("pack", time.perf_counter() - t0, nb)
        t0 = time.perf_counter()
        dd = dict_probe.place_device_dict(pd)
        for a in dd.device.values():
            a.block_until_ready()
        self._update("h2d", time.perf_counter() - t0, pd.nbytes)

        t0 = time.perf_counter()
        hits, any_hits = dict_probe.probe_value_hits(
            dd, [b"seed-value-0000512"])
        np.asarray(any_hits)
        self._update("compile", time.perf_counter() - t0, 0)
        t0 = time.perf_counter()
        hits, any_hits = dict_probe.probe_value_hits(
            dd, [b"seed-value-0000512"])
        np.asarray(any_hits)
        warm = time.perf_counter() - t0
        # a 2k-value probe is nearly all launch overhead; split it evenly
        # between the fixed dispatch cost and the per-byte rate so both
        # terms start on this host's scale
        self._update("dispatch", warm / 2, 0)
        self._update("device_probe", warm / 2, pd.nbytes)

    # ------------------------------------------------------------------
    # profiler feed (observability/profile.py listeners)

    def ingest_record(self, rec: dict) -> int:
        """One finished dispatch record (Dispatch.as_dict shape).
        dict_probe dispatches carry the probe-placement signal; scan
        dispatches feed the per-byte scan rate over their PHYSICAL
        staged bytes (packed residency moves the same pages into
        smaller size buckets, so the rate table splits by effective
        column width). Returns the number of model updates (the
        offline replay counts them)."""
        if not self.enabled or self._seeding:
            return 0
        mode = rec.get("mode")
        if mode in ("batched", "mesh", "coalesced"):
            stages = rec.get("stages_ms") or {}
            sb = int((rec.get("attrs") or {}).get("scan_bytes") or 0)
            ex = stages.get("execute")
            if ex and sb:
                self._update("scan", ex / 1e3, sb)
                return 1
            return 0
        if mode != "dict_probe":
            return 0
        stages = rec.get("stages_ms") or {}
        attrs = rec.get("attrs") or {}
        n = 0
        nb = int(attrs.get("probe_bytes") or 0)
        fp = attrs.get("fp")
        ex = stages.get("execute")
        if ex and nb:
            self.observe("device_probe", ex / 1e3, nb, fp=fp)
            n += 1
        comp = stages.get("compile")
        if comp:
            # the compile-stage dispatch call = trace+XLA compile + the
            # first run; book it whole as the compile cost (that IS what
            # an unseen shape pays)
            self._update("compile", comp / 1e3, 0)
            if nb:  # a compile record still resolves the decision
                self._resolve("device_probe", comp / 1e3, fp,
                              include_compile=True)
            n += 1
        lw = stages.get("lock_wait")
        if lw:
            self._update("collective", lw / 1e3, 0)
            n += 1
        return n

    def ingest_stage(self, stage: str, mode: str, seconds: float,
                     nbytes: int) -> int:
        """One out-of-record stage observation (profile.observe_stage
        listener): dictionary AND page-batch staging H2D — the batch
        observations carry PHYSICAL (packed) byte counts, so the
        staging-cost side of every decision scales with what actually
        crosses host→device, not the unpacked layout. The host prefilter
        is NOT harvested here — pipeline._probe_tags feeds it directly
        with the dictionary fingerprint attached (and also reports it
        to the profiler, where only the aggregate lands)."""
        if not self.enabled or self._seeding:
            return 0
        if stage == "h2d" and nbytes \
                and mode in ("dict_probe", "batched", "mesh"):
            self._update("h2d", seconds, nbytes)
            return 1
        return 0

    def ingest_profile_snapshot(self, snap: dict) -> int:
        """Offline calibration from a /debug/profile dump
        (scripts/calibrate_offload.py): replay the recent-dispatch ring
        through ingest_record, then seed the per-byte rates from the
        byte-carrying aggregates (mean seconds over mean bytes per
        observation). Returns observations ingested."""
        n = 0
        for rec in snap.get("recent") or []:
            n += self.ingest_record(rec)
        for mode, stages in (snap.get("aggregates") or {}).items():
            for stage, a in stages.items():
                cnt = int(a.get("count") or 0)
                nbytes = int(a.get("bytes") or 0)
                total_s = float(a.get("total_ms") or 0.0) / 1e3
                if not cnt or not nbytes:
                    continue
                kind = None
                if stage == "h2d" and mode == "dict_probe":
                    kind = "h2d"
                elif stage == "build" and mode == "host_probe":
                    kind = "host_probe"
                if kind is not None:
                    self._update(kind, total_s / cnt, nbytes // cnt)
                    n += 1
        return n

    # ------------------------------------------------------------------
    # operator surface

    def snapshot(self, recent: int = 32) -> dict:
        """/debug/planner payload: decisions, calibration state, and the
        cost-model table an operator can sanity-check crossovers from."""
        with self._lock:
            rates = {}
            for kind in PER_BYTE_KINDS:
                buckets = {
                    f"2^{2 * b}B": ev.value
                    for (k, b), ev in sorted(self._rates.items())
                    if k == kind and ev.value is not None
                }
                g = self._rates_global[kind]
                rates[kind] = {
                    "seconds_per_byte": g.value,
                    "observations": g.n,
                    "buckets": buckets,
                }
            fixed = {k: {"seconds": e.value, "observations": e.n}
                     for k, e in self._fixed.items()}
            ring = [d.as_dict() for d in list(self._ring)[-recent:]] \
                if recent > 0 else []
            return {
                "enabled": self.enabled,
                "seeded": self._seeded,
                "seed_ms": self._seed_ms,
                "decisions": dict(self._decisions),
                "mispredict": {
                    "observations": self._mispredict.n,
                    "ewma_abs_rel_error": self._mispredict.value,
                },
                "cost_model": {"rates": rates, "fixed": fixed},
                "recent": ring,
            }

    def reset(self) -> None:
        with self._lock:
            self._rates.clear()
            self._rates_global = {k: _Ewma() for k in PER_BYTE_KINDS}
            self._fixed = {k: _Ewma() for k in FIXED_KINDS}
            self._ring.clear()
            self._seq = 0
            self._seeded = False
            self._seeding = False
            self._seed_ms = None
            self._probe_observed = False
            self._decisions = {"host": 0, "device": 0}
            self._mispredict = _Ewma()


PLANNER = OffloadPlanner()
_listener_registered = False


def structural_node_seconds(node_bytes: dict) -> dict:
    """Structural plan nodes registered with the cost model: each node's
    byte estimate (structural.plan_node_bytes — leaf scans, the joins,
    segment reductions) through
    the live per-byte scan rate, the SAME EWMA the fused scan kernels
    calibrate via the dispatch-profiler feed. Consumed by the explain
    tree's est_ms column and the per-node device-seconds apportionment
    (one fused kernel has no per-node timer; the conserved split follows
    this model)."""
    return {nid: nb * PLANNER.rate("scan", nb)
            for nid, nb in node_bytes.items()}


def stage_veto(block, fp, n_shards: int = 1) -> bool:
    """True when the enabled planner places this dictionary's prefilter
    on HOST at staging time — call sites then skip packing/staging
    entirely. The stage-site decision of multiblock._pack_batch_dicts.
    Always False when the planner is disabled (the
    static-threshold behavior) — EXCEPT while the device circuit
    breaker blocks the device: then every staging is vetoed regardless
    of planner state, so a hung device is never handed a dictionary
    upload (robustness.breaker; one attribute read when closed)."""
    from tempo_tpu.robustness import BREAKER

    if BREAKER.blocking():
        return True
    if not PLANNER.enabled:
        return False
    S = max(1, int(n_shards))
    packed = getattr(block, "_device_dict_packed", None)
    packed_ok = packed is not None and packed.n_shards == S
    d = PLANNER.decide_probe(
        n_vals=len(block.val_dict),
        dict_bytes=dict_bytes_est(block.val_dict, cache_on=block),
        resident=False, packed=packed_ok,
        staged_bytes=(packed.nbytes if packed_ok else None),
        n_shards=S, fp=fp, site="stage")
    return d.target == "host"


def configure(enabled: bool | None = None, alpha: float | None = None,
              ring_size: int | None = None, seed: bool | None = None,
              reset: bool = False) -> OffloadPlanner:
    """Apply config (TempoDBConfig.search_offload_planner_*) to the
    process planner — the most recent TempoDB wins, matching how the
    profiler/metrics configure. Enabling registers the planner as a
    dispatch-profiler listener (its observation feed)."""
    global _listener_registered
    if reset:
        PLANNER.reset()
    if alpha is not None:
        PLANNER.alpha = float(alpha)
    if ring_size is not None:
        with PLANNER._lock:
            PLANNER._ring = deque(PLANNER._ring, maxlen=int(ring_size))
    if seed is not None:
        PLANNER.seed_on_first_use = bool(seed)
    if enabled is not None:
        PLANNER.enabled = bool(enabled)
        if enabled and not _listener_registered:
            from tempo_tpu.observability.profile import PROFILER

            PROFILER.add_listener(PLANNER.ingest_record)
            PROFILER.add_stage_listener(PLANNER.ingest_stage)
            _listener_registered = True
    return PLANNER
