"""The persistent compilation cache goes where JAX_COMPILATION_CACHE_DIR
says, or else to one fixed, git-ignored directory in the checkout."""
import os
import subprocess
import sys
from pathlib import Path

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_env_dir_receives_the_cache(tmp_path):
    """A compile after enable_compile_cache() lands in the directory
    the environment names (checked in a fresh process: the cache
    directory is read once, at the process's first compile)."""
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()\n")
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], env=env, text=True,
                         capture_output=True, timeout=120, check=True)
    assert out.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir()), "nothing was written to the cache"


def test_default_dir_is_fixed_and_ignored(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    assert compile_cache.compile_cache_dir() == str(REPO / ".jax_cache")
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
