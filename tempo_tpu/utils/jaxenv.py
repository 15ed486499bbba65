"""Where JAX's persistent compilation cache lives: one resolver.

Every entry point that compiles (cli/main, TempoDB, __graft_entry__) calls `enable_compile_cache()` before its first
compile and nothing else in the tree names a cache directory. The
location comes from outside: `JAX_COMPILATION_CACHE_DIR` when the
operator or harness sets it, otherwise one fixed directory in the
checkout. A cache directory that moves between runs (a temp dir, a
per-run WAL dir) never hits, which is why no caller passes a path.
"""

from __future__ import annotations

import os
import sys

DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

# the serving kernels at small shapes compile in 50-900 ms, below jax's
# 1 s default persistence threshold; cold start is the sum of many such
# compiles, so persist them too
_MIN_COMPILE_TIME_S = 0.1


def compile_cache_dir() -> str:
    """`JAX_COMPILATION_CACHE_DIR` if set, else the fixed git-ignored
    `<checkout>/.jax_cache`."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or DEFAULT_COMPILE_CACHE_DIR)


def _adopt_entries_without_atime(path: str) -> None:
    """Give every cache entry that lacks one its access-time file.

    With `jax_compilation_cache_max_size` set, jax keeps `<key>-atime`
    beside each `<key>-cache` and reads them all before every write (its
    LRU eviction). A directory filled while the limit was off has none,
    and then EVERY later write fails on the first such entry
    (FileNotFoundError on its `-atime`, a warning per compile): nothing
    new is ever persisted and each restart compiles again. Seen in PR
    26's chip runs (PERF.md): a checkout's `.jax_cache`, filled without
    a limit, on a machine whose environment sets one; every run compiled
    53 s of mesh kernels again. An adopted entry gets the oldest possible
    time, so it is the first to go when the directory outgrows the
    limit."""
    try:
        names = set(os.listdir(path))
    except OSError:
        return
    for name in names:
        if not name.endswith("-cache"):
            continue
        atime = name[:-len("-cache")] + "-atime"
        if atime not in names:
            try:
                with open(os.path.join(path, atime), "wb") as f:
                    f.write((0).to_bytes(8, "little"))
            except OSError:
                return  # read-only directory: jax will say so itself


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at `compile_cache_dir()`
    so a process restart replays XLA compiles from disk instead of
    re-paying them, and book on-disk hits as
    tempo_search_jit_cache_events_total{result="persisted"}. Idempotent;
    call before the first compile (jax pins its cache object there).
    Returns the directory, or "" when it cannot be created — the cache
    is an optimization and must not stop a server from starting."""
    import jax

    from tempo_tpu.observability.profile import (
        watch_persistent_compile_cache,
    )

    path = compile_cache_dir()
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as e:
        print(f"warning: persistent compile cache disabled ({e})",
              file=sys.stderr)
        return ""
    # jax reads the env var at import; this is a no-op then, and the
    # only value ever written otherwise is the resolver's
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    if jax.config.jax_compilation_cache_max_size != -1:
        _adopt_entries_without_atime(path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _MIN_COMPILE_TIME_S)
    watch_persistent_compile_cache()
    return path
