"""Placement of the persistent compilation cache (``repro.compile_cache``).

The entry points call ``enable_compile_cache()`` before their first
compile: ``JAX_COMPILATION_CACHE_DIR`` wins when set, otherwise the cache
lives at a fixed directory inside the checkout.  Importing the library
sets no cache.  The subprocess cases run on the CPU backend so that each
starts with a fresh JAX configuration.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _python(code: str, **env) -> str:
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    base.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **env)
    out = subprocess.run([sys.executable, "-c", code], env=base, check=True,
                         capture_output=True, text=True, timeout=120)
    return out.stdout.strip().splitlines()[-1]


def test_env_dir_is_left_alone_and_receives_the_cache(tmp_path):
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.compile_cache import enable_compile_cache\n"
            "path = enable_compile_cache()\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()\n"
            "print(path, jax.config.jax_compilation_cache_dir)\n")
    out = _python(code, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                  JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert out == f"{tmp_path} {tmp_path}"
    assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())


def test_fixed_in_checkout_dir_without_env(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_importing_the_library_sets_no_cache():
    code = ("import jax\n"
            "import repro.core.scan_engine, repro.experiment, "
            "repro.kernels.ops\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    assert _python(code) == "None"
