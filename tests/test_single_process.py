"""One process per chip: no entry point starts a JAX child from a parent
that may hold the chip."""
from __future__ import annotations

import inspect
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def test_validation_runs_in_process():
    from repro.validate import run_validation
    from repro.validate.__main__ import main

    assert "processes" not in inspect.signature(run_validation).parameters
    with pytest.raises(SystemExit):
        main(["--processes", "2"])


def test_rss_probes_refuse_a_chip_host(monkeypatch):
    from benchmarks.reuse_throughput import _rss_probe

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        _rss_probe("baseline", 1024, lines=64, window=256)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        _rss_probe("baseline", 1024, lines=64, window=256)
