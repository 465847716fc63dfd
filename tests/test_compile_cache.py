"""The persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when set,
else one fixed, gitignored directory in the checkout.

Each case compiles in a fresh subprocess, since the cache directory is
process-global JAX state."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch import compile_cache

ROOT = os.path.join(os.path.dirname(__file__), "..")

CHILD = """
import sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
compile_cache.CACHE_DIR = sys.argv[1]      # where "the checkout" is
print(compile_cache.enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(3)).block_until_ready()
"""


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "default"])
def test_cache_written_only_to_its_directory(tmp_path, env_set):
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", CHILD, str(default_dir)], env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    want, other = (env_dir, default_dir) if env_set else (default_dir,
                                                           env_dir)
    assert out.stdout.strip() == str(want)
    assert any(want.iterdir()), "nothing was cached"
    assert not other.exists()


def test_default_directory_is_fixed_and_gitignored():
    assert compile_cache.CACHE_DIR == Path(ROOT).resolve() / ".jax_cache"
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
