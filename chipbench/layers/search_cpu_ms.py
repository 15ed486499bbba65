"""Host process: CPU milliseconds a search costs the host: `cpu_self`
(a span's `thread.cpu_ns` less that of the spans of its thread inside
it) summed over the window's search traces, over those traces."""
from chipbench.layers.hostcpu import search_cpu_ms as compute  # noqa: F401
