"""Arithmetic the readers share: counter text -> numbers, percentiles,
span self times. Kept with the benchmark so that no PR that claims a
gain can change how a number is made."""

from __future__ import annotations

import math
import re

FAILED_LATENCY_MS = 1.0e9    # a failed request misses every latency limit


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {name: {label-string: value}}."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = re.match(r"([A-Za-z_:][\w:]*)(\{[^}]*\})?\s+(\S+)", line)
        if not m:
            continue
        try:
            v = float(m.group(3))
        except ValueError:
            continue
        out.setdefault(m.group(1), {})[m.group(2) or ""] = v
    return out


def metric_sum(metrics: dict, name: str, **labels) -> float:
    want = [f'{k}="{v}"' for k, v in labels.items()]
    return sum(v for lab, v in metrics.get(name, {}).items()
               if all(w in lab for w in want))


def delta(run: dict, name: str, **labels) -> float:
    """Counter movement over the measured window."""
    c = run["counters"]
    return (metric_sum(c["after"], name, **labels)
            - metric_sum(c["before"], name, **labels))


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile of all values (q in (0, 100])."""
    if not values:
        return float("nan")
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q / 100.0 * len(s)) - 1))]


def latencies_ms(run: dict, op: str) -> list:
    """Latency of every window request whose op's name starts with
    `op` (`search` covers every search op), from when it was due; a
    request that failed counts with FAILED_LATENCY_MS."""
    out = []
    for r in run["records"]:
        if not run["requests"][r["i"]]["op"].startswith(op):
            continue
        out.append((r["done"] - r["due"]) * 1e3 if r["status"] == 200
                   else FAILED_LATENCY_MS)
    return out


def count_ok(run: dict, op: str) -> int:
    return sum(1 for r in run["records"] if r["status"] == 200
               and run["requests"][r["i"]]["op"].startswith(op))


def self_times_ms(spans: list, names) -> list:
    """Self time of every span whose name is in `names`: its duration
    less the part of it that its child spans cover (children may
    overlap one another; the union is taken)."""
    kids: dict = {}
    for s in spans:
        if s["parent_id"]:
            kids.setdefault(s["parent_id"], []).append(s)
    out = []
    for s in spans:
        if s["name"] not in names:
            continue
        iv = sorted((max(c["start_ns"], s["start_ns"]),
                     min(c["end_ns"], s["end_ns"]))
                    for c in kids.get(s["span_id"], []))
        covered, hi = 0, s["start_ns"]
        for a, b in iv:
            a = max(a, hi)
            if b > a:
                covered += b - a
                hi = b
        out.append((s["end_ns"] - s["start_ns"] - covered) / 1e6)
    return out


def durations_ms(spans: list, names) -> list:
    return [(s["end_ns"] - s["start_ns"]) / 1e6 for s in spans
            if s["name"] in names]


def median(values: list):
    if not values:
        return None
    s = sorted(values)
    n = len(s)
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2


def scan_programs(trace: dict) -> tuple:
    """(device nanoseconds, launches) of the scan programs in a reduced
    trace: every jit program whose name has `scan_kernel` in it (the
    batched, the fused and the one-block kernel, and their mesh forms)."""
    ns = sum(v for k, v in trace["programs_ns"].items() if "scan_kernel" in k)
    n = sum(v for k, v in trace["program_calls"].items()
            if "scan_kernel" in k)
    return ns, n
