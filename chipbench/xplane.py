"""Profiler trace -> device busy intervals, idle share, time per named
jit program, the busiest device operations and the longest idle gaps
with what the host was doing in them.

Reads the `.xplane.pb` that `jax.profiler.start_trace/stop_trace` write,
with nothing but JAX (`jax.profiler.ProfileData`). A device plane is a
plane named `/device:...` that has an `XLA Ops` or `XLA Modules` line.
Busy time is the union of the op events' intervals (nested and
overlapping events count once); a program's time is the sum of its
events on the `XLA Modules` line, its name the event's name up to the
first `(`. Times are nanoseconds from the start of the profile.

The measured window is what lies between two host annotations that the
harness writes (`SYNC_BEGIN`, `SYNC_END`); the wall-clock time taken
inside `SYNC_BEGIN` ties the profile's clock to the program's spans.
"""

from __future__ import annotations

import glob
import os

SYNC_BEGIN = "chipbench_window_begin"
SYNC_END = "chipbench_window_end"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_trace(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def merge(intervals: list) -> list:
    """Union of [start, end) intervals, sorted, disjoint."""
    out: list = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def program_name(event_name: str) -> str:
    return event_name.split("(", 1)[0].strip()


def op_name(event_name: str) -> str:
    """`%fusion.2 = pred[...] fusion(...)` -> `fusion.2`: the trace names
    an op by its whole HLO line."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()[:80]


def _find_marker(profile, name: str):
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    return float(ev.start_ns)
    return None


def reduce(profile) -> dict:
    """Everything the per-layer readers and the result line need."""
    lo = _find_marker(profile, SYNC_BEGIN)
    hi = _find_marker(profile, SYNC_END)
    devices = []
    programs: dict = {}
    calls: dict = {}
    ops: dict = {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines and MODULES_LINE not in lines:
            continue
        busy_line = lines.get(OPS_LINE) or lines[MODULES_LINE]
        events = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                   e.name) for e in busy_line.events]
        devices.append({"name": plane.name,
                        "intervals": [[a, b] for a, b, _ in events]})
        for a, b, name in events:
            if _inside(a, b, lo, hi):
                name = op_name(name)
                ops[name] = ops.get(name, 0.0) + (b - a)
        for e in (lines[MODULES_LINE].events
                  if MODULES_LINE in lines else ()):
            a, b = float(e.start_ns), float(e.start_ns + e.duration_ns)
            if _inside(a, b, lo, hi):
                n = program_name(e.name)
                programs[n] = programs.get(n, 0.0) + (b - a)
                calls[n] = calls.get(n, 0) + 1
    if lo is None or hi is None:
        ends = [b for d in devices for _, b in d["intervals"]]
        starts = [a for d in devices for a, _ in d["intervals"]]
        lo = min(starts) if starts else 0.0
        hi = max(ends) if ends else 0.0
    for d in devices:
        d["intervals"] = merge(clip(d["intervals"], lo, hi))
        d["busy_ns"] = sum(b - a for a, b in d["intervals"])
    n = max(1, len(devices))
    return {
        "window_ns": hi - lo, "begin_ns": lo, "end_ns": hi,
        "devices": devices,
        "busy_ns": sum(d["busy_ns"] for d in devices) / n,
        "programs_ns": programs, "program_calls": calls,
        "ops_ns": sorted(ops.items(), key=lambda kv: -kv[1]),
    }


def _inside(a, b, lo, hi) -> bool:
    return (lo is None or a >= lo) and (hi is None or b <= hi)


def idle_gaps(reduced: dict) -> list:
    """[(start_ns, end_ns)] of the first device's idle gaps in the
    window, longest first."""
    if not reduced["devices"]:
        return []
    iv = reduced["devices"][0]["intervals"]
    edges = [reduced["begin_ns"]] + [x for ab in iv for x in ab] + [
        reduced["end_ns"]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return sorted(gaps, key=lambda g: g[0] - g[1])


def attribute_gaps(gaps: list, spans: list, profile_zero_wall_ns: float,
                   top: int = 10) -> list:
    """[[what the host was doing, seconds], ...] for the longest gaps,
    summed by name. A gap is named by the program span that was open for
    the largest part of it, deepest first; `(no span open)` if none."""
    out: dict = {}
    by_id = {s["span_id"]: s for s in spans}

    def depth(s):
        d = 0
        while s.get("parent_id") and s["parent_id"] in by_id and d < 32:
            s = by_id[s["parent_id"]]
            d += 1
        return d

    for a, b in gaps[:200]:
        wa, wb = a + profile_zero_wall_ns, b + profile_zero_wall_ns
        best, best_key = "(no span open)", (0.0, -1)
        for s in spans:
            ov = min(wb, s["end_ns"]) - max(wa, s["start_ns"])
            if ov <= 0:
                continue
            key = (round(ov / (wb - wa), 2), depth(s))
            if key > best_key:
                best, best_key = s["name"], key
        out[best] = out.get(best, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(out.items(),
                                      key=lambda kv: -kv[1])[:top]]
