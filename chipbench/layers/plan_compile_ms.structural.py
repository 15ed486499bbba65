"""Batcher + coalescer: host time a completed search spends lowering its
query onto the group's blocks: summed `structural.compile` spans
(`compile_structural`: the IR walked into a plan, its leaf terms probed
against each distinct dictionary, the per-block tables assembled; once a
group on a prepare-memo miss) over the searches completed in the window.
A program without the span gives nothing to read."""
from chipbench.lib import count_ok
from chipbench.layers.spans import ms, named


def compute(run):
    spans = named(run["spans"], "structural.compile")
    n = count_ok(run, "search")
    return sum(ms(s) for s in spans) / n if spans and n else None
