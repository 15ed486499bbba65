"""What the readers of the many-tenant cell share: the launches of the
traced seconds, and a launch's real pages.

The profiler runs `trace["window_ns"]` in the middle of the window
(`run.trace_window`), and `run["trace"]` keeps no offset between its
clock and the spans': the spans all lie in the window (the tracer is
installed for it alone), so the traced seconds are taken as the middle
`window_ns` of the spans' extent. The launch mix is steady over a
window; what the choice of seconds moves is sampling noise."""


def launches(run, name="dispatch.execute", traced=True):
    """The `name` spans that carry `blocks`: with `traced`, those of the
    traced seconds where the run has a trace; else all of the window."""
    spans = [s for s in run["spans"]
             if s["name"] == name and s["attributes"].get("blocks")]
    trace = run.get("trace")
    if not (traced and spans and trace and trace.get("window_ns")):
        return spans
    lo = min(s["start_ns"] for s in run["spans"])
    hi = max(s["end_ns"] for s in run["spans"])
    mid, half = (lo + hi) / 2, trace["window_ns"] / 2
    inside = [s for s in spans
              if mid - half <= s["start_ns"] and s["end_ns"] <= mid + half]
    return inside or spans


def pages_per_block(manifest) -> float:
    return manifest["pages"] / len(manifest["block_ids"])
