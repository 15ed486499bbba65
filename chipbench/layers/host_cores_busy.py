"""Host process: cores the process kept busy in the window: the delta
of `process_cpu_seconds_total` over the window's wall seconds. Near 1.0
with sixteen callers waiting says one interpreter lock is the limit,
not the chip. Every thread counts, the benchmark's own too: in a traced
run the profiler and the read of its trace."""
from chipbench.layers.hostcpu import cores_busy as compute  # noqa: F401
