"""The persistent compilation cache lives at one fixed place:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.cache/jax``."""
from __future__ import annotations

import pytest

import jax

from repro import compile_cache
from repro.compile_cache import cache_entries, enable_compile_cache

_OPTIONS = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_config():
    """Put the process-wide cache options back after the test."""
    from jax.experimental.compilation_cache import compilation_cache

    saved = {name: getattr(jax.config, name) for name in _OPTIONS}
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def test_default_dir_is_fixed_under_the_repo():
    assert compile_cache.DEFAULT_CACHE_DIR == (
        compile_cache.REPO_ROOT / ".cache" / "jax"
    )
    assert (compile_cache.REPO_ROOT / "chip_smoke.py").is_file()


def test_without_env_uses_the_default_dir(tmp_path, monkeypatch,
                                          restore_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_CACHE_DIR", tmp_path / "jax")
    path = enable_compile_cache()
    assert path == tmp_path / "jax" and path.is_dir()
    assert jax.config.jax_compilation_cache_dir == str(path)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_env_dir_wins_and_no_other_is_set(tmp_path, monkeypatch,
                                          restore_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "env"))
    assert enable_compile_cache() == tmp_path / "env"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_entries_counts_compiled_programs(tmp_path):
    for name in ("a-cache", "a-atime", "b-cache"):
        (tmp_path / name).write_bytes(b"x")
    assert cache_entries(tmp_path) == 2
