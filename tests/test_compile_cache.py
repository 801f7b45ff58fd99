"""The persistent compilation cache is placed from outside: in
`$JAX_COMPILATION_CACHE_DIR` when set, else at one fixed, git-ignored path
inside the checkout (src/repro/compile_cache.py)."""
import os
import subprocess
import sys
from pathlib import Path

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]

PROBE = """
import jax
from repro import compile_cache
path = compile_cache.enable()
jax.jit(lambda x: x * 3 + 1)(2.0).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(env_dir):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop(compile_cache.ENV, None)
    if env_dir is not None:
        env[compile_cache.ENV] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_env_dir_is_used_and_nothing_else_is_set(tmp_path):
    path, configured = _probe(tmp_path)
    assert path == configured == str(tmp_path)
    assert any(tmp_path.iterdir()), "the compile was not cached there"


def test_default_is_the_fixed_git_ignored_repo_path():
    path, configured = _probe(None)
    assert path == configured == str(ROOT / ".jax_cache")
    assert compile_cache.REPO_CACHE == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
