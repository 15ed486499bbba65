"""Batcher + coalescer: CPU milliseconds a kernel launch costs the
host, whoever waits meanwhile: mean `thread.cpu_ns` of
`coalescer.launch` and of its `dispatch.<stage>` children where the
dispatch watchdog ran them on a thread of its own."""
from chipbench.layers.hostcpu import launch_cpu_ms as compute  # noqa: F401
