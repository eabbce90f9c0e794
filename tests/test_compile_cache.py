"""The entry points' persistent compile cache (launch/compile_cache.py).

Each case runs in a fresh interpreter: the cache directory is process-wide
JAX state, and pointing this worker's cache somewhere would leak into
every later test it runs.
"""
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.normpath(os.path.join(HERE, os.pardir, "src"))

_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache
used = enable_compile_cache()
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
print(used)
print(jax.config.jax_compilation_cache_dir)
print(REPO_CACHE_DIR)
"""


def _run(env_extra, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _PROBE.format(compile=compile_)],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_env_dir_wins_and_receives_the_cache(tmp_path):
    used, configured, _repo = _run(
        {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}, compile_=True)
    assert used == configured == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry written"


def test_default_dir_is_fixed_inside_the_checkout():
    used, configured, repo_dir = _run({}, compile_=False)
    assert used == configured == repo_dir
    root = os.path.normpath(os.path.join(HERE, os.pardir))
    assert repo_dir == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore"), encoding="utf-8") as f:
        assert ".jax_cache/" in f.read().split()
