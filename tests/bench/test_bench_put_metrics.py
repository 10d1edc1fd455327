"""The per-layer metrics that read the packed SDCM input transfer
(``sdcm.put`` spans): each reader on a synthetic snapshot, on a program
that records no ``sdcm.put``, and read from a traced smoke run on the
CPU."""
from __future__ import annotations

import sys
import time

import pytest

from bench import harness

from bench_cells import ROOT

SPANS = {
    "explore.evaluate": (2, 0.100, 0.010, 1280),
    "sdcm.dispatch": (24, 0.020, 0.020, 1280),
    "sdcm.put": (24, 0.005, 0.005, 24 * 64 * 18 * 4),
}

EXPECTED = {
    "puts_per_dispatch.sweep": 1.0,
    "put_us_per_config.sweep": 0.005 * 1e6 / 1280,
}


@pytest.fixture(autouse=True)
def clean_recorder():
    from repro import telemetry

    telemetry.reset()
    yield
    telemetry.reset()


def _reader(name):
    return harness.load_module(ROOT / "bench" / "metrics" / f"{name}.py")


def _snapshot(spans):
    return {k: {"count": c, "total_s": t, "self_s": s, "n": n}
            for k, (c, t, s, n) in spans.items()}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_put_reader_on_a_synthetic_snapshot(name, monkeypatch):
    from repro import telemetry

    reader = _reader(name)
    monkeypatch.setattr(telemetry, "snapshot", lambda: _snapshot(SPANS))
    traced = harness.RunContext(seed=1, seconds=1.0, trace=True)
    assert reader.read(traced) == pytest.approx(EXPECTED[name])
    untraced = harness.RunContext(seed=1, seconds=1.0, trace=False)
    assert reader.read(untraced) is None
    monkeypatch.setattr(telemetry, "snapshot", lambda: {})
    assert reader.read(traced) is None
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert reader.read(traced) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_put_readers_read_nothing_without_the_put_span(name, monkeypatch):
    """A program that dispatches without the packed ``sdcm.put``
    transfer reads nothing."""
    from repro import telemetry

    spans = {k: v for k, v in SPANS.items() if k != "sdcm.put"}
    monkeypatch.setattr(telemetry, "snapshot", lambda: _snapshot(spans))
    traced = harness.RunContext(seed=1, seconds=1.0, trace=True)
    assert _reader(name).read(traced) is None


def test_traced_smoke_sweep_reads_both_put_metrics(smoke):
    cell = smoke("sweep.exhaustive", workloads=2)
    out = harness.run_cell(cell, 2**31 + 11, 0.5, True,
                           started=time.perf_counter(),
                           log=lambda *_a: None)
    assert out["correct"] is True
    wanted = {m["name"] for m in cell.per_layer} & set(EXPECTED)
    assert wanted == set(EXPECTED)
    assert out["metrics"]["puts_per_dispatch.sweep"]["value"] == 1.0
    assert out["metrics"]["put_us_per_config.sweep"]["value"] > 0
