"""Where the entry points keep JAX's persistent compilation cache:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
Each case runs in a child process (the cache directory is process-wide
JAX state) on a copy of the package, so nothing is written into this
checkout."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

_CHILD = """
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
import jax, jax.numpy as jnp
jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(jnp.arange(4)))
"""


@pytest.fixture
def checkout(tmp_path):
    shutil.copytree(ROOT / "src", tmp_path / "checkout" / "src",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path / "checkout"


def _run(checkout: Path, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(PYTHONPATH=str(checkout / "src"), JAX_PLATFORMS="cpu",
                # cache every program, however small or quick to compile
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0", **env)
    res = subprocess.run([sys.executable, "-c", _CHILD], env=full,
                         cwd=checkout, capture_output=True, text=True,
                         timeout=120)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout.strip().splitlines()[-1]


def test_env_dir_is_used(checkout, tmp_path):
    cache = tmp_path / "elsewhere"
    assert _run(checkout, JAX_COMPILATION_CACHE_DIR=str(cache)) == str(cache)
    assert any(cache.iterdir())
    assert not (checkout / ".jax_cache").exists()


def test_default_dir_is_in_checkout(checkout):
    assert _run(checkout) == str(checkout / ".jax_cache")
    assert any((checkout / ".jax_cache").iterdir())


def test_default_dir_is_ignored_by_git():
    lines = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in lines
