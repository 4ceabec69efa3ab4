"""Where the entry points put JAX's persistent compilation cache, and the
TPU smoke's refusal to run anywhere else."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.compile_cache import use_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_placed_cache_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_one_ignored_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        assert use_compile_cache() == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert Path(first) == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_chip_smoke_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=120,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
