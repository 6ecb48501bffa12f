"""Where the persistent compilation cache goes."""

import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_dir_from_env(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_cache_dir_default_is_fixed_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    second = enable_compile_cache()
    assert first == second == str(CHECKOUT / ".jax_cache") == str(DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == first
    ignored = (CHECKOUT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_cache_is_written_to_env_dir(tmp_path):
    """A compile after the helper lands in the directory the variable names
    (in a child process: the cache is set up once per process)."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
    """)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path), JAX_PLATFORMS="cpu",
               PYTHONPATH=str(CHECKOUT / "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    assert any(tmp_path.iterdir())
