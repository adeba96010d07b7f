"""Persistent compile-cache placement (``repro.launch.compile_cache``).

Run in subprocesses: the helper sets process-global JAX config.
"""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.launch.compile_cache import REPO_CACHE_DIR, setup_compile_cache
used = setup_compile_cache()
print("USED", used)
print("CONFIG", jax.config.jax_compilation_cache_dir)
print("REPO", REPO_CACHE_DIR)
if "--compile" in __import__("sys").argv:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.ones(4)))
"""


def _run(env_updates, *args):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_updates, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return dict(line.split(" ", 1) for line in out.stdout.splitlines())


def test_env_dir_wins_and_receives_entries(tmp_path):
    cache = tmp_path / "x"
    got = _run({"JAX_COMPILATION_CACHE_DIR": str(cache)}, "--compile")
    assert got["USED"] == got["CONFIG"] == str(cache)
    assert any(p.name.endswith("-cache") for p in cache.iterdir())


def test_default_is_fixed_path_in_checkout():
    first, second = _run({}), _run({})
    assert first["USED"] == first["CONFIG"] == first["REPO"]
    assert first["USED"] == os.path.join(ROOT, ".jax_cache")
    assert second["USED"] == first["USED"]
