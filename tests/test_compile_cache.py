"""The persistent compilation cache helper the entry points call."""

import os
import pathlib
import subprocess
import sys

import jax

from repro.launch import compile_cache
from repro.launch.compile_cache import enable_compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_default_dir_is_fixed_under_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path      # same place every run
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_overrides_and_helper_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_entries_land_in_the_env_dir(tmp_path):
    # a fresh process: JAX reads the variable itself, and the helper's
    # call leaves it in charge
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "PYTHONPATH": str(REPO / "src")}
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert any((tmp_path / "cache").iterdir())


def test_import_changes_no_config():
    import importlib
    before = jax.config.jax_compilation_cache_dir
    importlib.reload(compile_cache)
    assert jax.config.jax_compilation_cache_dir == before
