"""Where the persistent compile cache lives (utils/jaxenv.py).

One resolver: `JAX_COMPILATION_CACHE_DIR` wins and nothing else is set;
unset, the cache is one fixed git-ignored directory in the checkout. A
directory that moves between runs never hits, so no code may name a
cache location of its own: the static check below reads every Python
file the repo ships.

jax's cache config is process-global, so the end-to-end cases run in a
subprocess each.
"""

import ast
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESOLVER = os.path.join("tempo_tpu", "utils", "jaxenv.py")

_CHILD = (
    "import jax\n"
    "from tempo_tpu.backend import LocalBackend\n"
    "from tempo_tpu.db import TempoDB, TempoDBConfig\n"
    "import sys\n"
    "TempoDB(LocalBackend(sys.argv[1] + '/blocks'), sys.argv[1] + '/wal',"
    " TempoDBConfig())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def _cache_dir_after_tempodb(tmp_path, env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CHILD, str(tmp_path)],
                         env=env, cwd=REPO, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-800:]
    return out.stdout.strip().splitlines()[-1]


def test_env_wins_and_nothing_else_is_set(tmp_path):
    want = str(tmp_path / "outside-cache")
    assert _cache_dir_after_tempodb(tmp_path, want) == want
    assert os.path.isdir(want)
    # nothing was placed under the (per-run) WAL directory
    assert not (tmp_path / "wal" / "host-state" / "xla-cache").exists()


def test_default_is_one_fixed_gitignored_path_in_the_checkout(tmp_path):
    from tempo_tpu.utils.jaxenv import DEFAULT_COMPILE_CACHE_DIR

    assert DEFAULT_COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    got = _cache_dir_after_tempodb(tmp_path, None)
    assert got == DEFAULT_COMPILE_CACHE_DIR
    assert not got.startswith(str(tmp_path))


_LIMITED_CHILD = (
    "import jax, jax.numpy as jnp, warnings\n"
    "from tempo_tpu.utils.jaxenv import enable_compile_cache\n"
    "warnings.simplefilter('error')\n"
    "enable_compile_cache()\n"
    "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)\n"
    "jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)\n"
    "print(jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).sum())\n"
)


def test_a_size_limit_over_a_directory_filled_without_one(tmp_path):
    """Entries written while `jax_compilation_cache_max_size` was off
    have no `-atime` file; with the limit on, jax reads every entry's
    before each write and fails on the first that is missing, so nothing
    new is persisted. The resolver adopts them (oldest first to go),
    only when a limit is set."""
    cache = tmp_path / "cache"
    cache.mkdir()
    (cache / "jit_old-0123-cache").write_bytes(b"x" * 64)
    (cache / "jit_kept-4567-cache").write_bytes(b"y" * 64)
    (cache / "jit_kept-4567-atime").write_bytes((9).to_bytes(8, "little"))

    def child(limit):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(cache))
        env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
        if limit:
            env["JAX_COMPILATION_CACHE_MAX_SIZE"] = str(limit)
        return subprocess.run([sys.executable, "-c", _LIMITED_CHILD], env=env,
                              cwd=REPO, capture_output=True, text=True,
                              timeout=120)

    out = child(None)
    assert out.returncode == 0, out.stderr[-800:]
    assert not (cache / "jit_old-0123-atime").exists()
    before = set(os.listdir(cache))
    out = child(1 << 30)
    # a write that failed would have warned, and warnings are errors there
    assert out.returncode == 0, out.stderr[-800:]
    assert (cache / "jit_old-0123-atime").read_bytes() == bytes(8)
    assert (cache / "jit_kept-4567-atime").read_bytes()[0] == 9
    new = set(os.listdir(cache)) - before - {"jit_old-0123-atime"}
    assert any(n.endswith("-atime") for n in new), sorted(new)


def _shipped_python_files():
    skip = {".git", "tests", "chiprun_out", ".smoke_checkout", ".jax_cache",
            "__pycache__"}
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip]
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(root, f)


def test_no_code_but_the_resolver_names_a_cache_location():
    """Static: the two spellings of the setting appear as string
    constants in utils/jaxenv.py only, and every call of the resolver
    passes nothing — so no path from mkdtemp, a TemporaryDirectory, a
    WAL directory, a pid or the clock can reach it."""
    offenders = []
    for path in _shipped_python_files():
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            tree = ast.parse(f.read(), filename=rel)
        for node in ast.walk(tree):
            if (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and node.value.lower() == "jax_compilation_cache_dir"
                    and rel != RESOLVER):
                offenders.append(f"{rel}:{node.lineno} names the setting")
            if isinstance(node, ast.Call):
                fn = node.func
                name = getattr(fn, "attr", getattr(fn, "id", ""))
                if name == "enable_compile_cache" and (
                        node.args or node.keywords):
                    offenders.append(f"{rel}:{node.lineno} passes a path")
    assert not offenders, offenders
