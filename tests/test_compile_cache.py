"""The persistent compilation cache helper and the chip smoke's refusal
to run without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.compile_cache import enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the env


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        first = enable_compile_cache()
        assert enable_compile_cache() == first == ROOT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(first)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr
