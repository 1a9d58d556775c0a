"""Placement of the persistent compile cache (``repro.compile_cache``)."""
import os
import subprocess
import sys

import jax

from repro import compile_cache


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_uses_fixed_repo_path(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.use_compile_cache()
        assert path == os.path.join(compile_cache.REPO_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(compile_cache.REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cache_lands_in_env_dir(tmp_path):
    """A compile in a fresh process writes its entry under the directory
    the environment names."""
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.compile_cache import use_compile_cache\n"
            "use_compile_cache()\n"
            "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               PYTHONPATH=os.path.join(compile_cache.REPO_ROOT, "src"))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=300)
    assert any((tmp_path / "cc").iterdir())
