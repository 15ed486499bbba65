"""Entries inspected by the searches that completed, per second of the
window (first send to last answer): what a chip is worth when served."""


def compute(run):
    done = [r["done"] for r in run["records"] if r["status"] == 200]
    n = run["work"].get("inspected_entries", 0)
    return n / max(done) if done and n else None
