"""Fixtures of the benchmark's tests (helpers in ``bench_cells.py``)."""
from __future__ import annotations

import pytest

from bench_cells import smoke_cell


@pytest.fixture
def smoke():
    return smoke_cell


@pytest.fixture(autouse=True)
def _no_persistent_compile_cache(monkeypatch):
    """Runs in the test process leave JAX's cache settings as they were."""
    import repro.compile_cache

    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: None)
