"""Staging: host-to-device rate of the re-stages, bytes over seconds of
the `batcher.place` spans (one per put of a group's columns, fenced:
the span ends when the bytes are on the device). No share of a peak:
`peaks.json` has no host-link peak to be a share of."""
from chipbench.layers.spans import named


def compute(run):
    spans = [s for s in named(run["spans"], "batcher.place")
             if s["attributes"].get("bytes")]
    ns = sum(s["end_ns"] - s["start_ns"] for s in spans)
    if not ns:
        return None
    return sum(s["attributes"]["bytes"] for s in spans) / ns
