"""Placement of JAX's persistent compilation cache (repro/compile_cache.py):
an environment-given directory wins, otherwise a fixed directory in the
checkout; importing the library never configures it."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_wins_and_config_is_left_alone(monkeypatch, tmp_path,
                                               restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.use_compile_cache() == want       # stable


def test_cache_entries_land_in_env_dir(tmp_path):
    """A fresh process that calls the entry-point hook before compiling
    writes its cache entries into the environment-given directory."""
    code = ("from repro.compile_cache import use_compile_cache\n"
            "use_compile_cache()\n"
            "import jax, jax.numpy as jnp\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(REPO / "src"))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert any(tmp_path.iterdir()), "no cache entry written"


def test_import_does_not_configure_cache():
    code = ("import jax\n"
            "before = jax.config.jax_compilation_cache_dir\n"
            "import revet, repro.compile_cache\n"
            "assert jax.config.jax_compilation_cache_dir == before\n")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
