"""Host process: how much of the process's CPU in the window the spans
explain: summed `cpu_self` over the delta of
`process_cpu_seconds_total`. The rest burns where no span looks: the
poll, the watcher, GC, jax's own threads, the load generator's parent,
in a traced run the profiler."""
from chipbench.layers.hostcpu import spanned_cpu_share as compute  # noqa: F401
