"""Batcher + coalescer: the share of the searches' time in the batcher
in which a thread doing their work was off a core in code that has no
reason to sleep (the interpreter lock, or the kernel's scheduler): off-
core self time of the spans under `batcher.Search` outside
`hostcpu.NAMED_WAITS`, over summed `batcher.Search`."""
from chipbench.layers.hostcpu import (  # noqa: F401
    unnamed_offcore_share as compute)
