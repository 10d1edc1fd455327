"""JAX's persistent compilation cache, kept at one fixed place.

A cold start on the chip is mostly compilation.  Entry points (the
``repro.service``, ``repro.validate`` and ``repro.explore`` CLIs,
``benchmarks/run.py`` and ``chip_smoke.py``) call
:func:`enable_compile_cache` first, so every later process of the same
checkout finds what an earlier one compiled.  Nothing calls it at
import: a library user keeps whatever cache policy they chose.

The cache key includes the directory, so the directory never moves:
``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself, and
no other directory is set in code), otherwise ``<repo>/.cache/jax``.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_CACHE_DIR = REPO_ROOT / ".cache" / "jax"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> Path:
    """Turn the persistent compilation cache on; return its directory.

    Every compilation is cached, however short: on the chip a run
    compiles many small kernels, and together they dominate a cold
    start.
    """
    import jax

    env = os.environ.get(ENV_VAR)
    path = Path(env) if env else DEFAULT_CACHE_DIR
    path.mkdir(parents=True, exist_ok=True)
    if not env:
        jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def cache_entries(path: Path) -> int:
    """Number of compiled programs stored under ``path``."""
    return sum(1 for _ in Path(path).glob("*-cache"))
