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
