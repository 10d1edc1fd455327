"""The per-layer metrics that read the program's own spans
(``repro.telemetry``): each reader on a synthetic snapshot, and every
one of them read from a traced smoke run on the CPU."""
from __future__ import annotations

import sys
import time

import pytest

from bench import harness

from bench_cells import ROOT

SPANS = {
    "explore.evaluate": (2, 0.100, 0.010, 1280),
    "explore.geometry": (8, 0.020, 0.020, 1280),
    "sdcm.sweep": (8, 0.060, 0.030, 1280),
    "sdcm.dispatch": (24, 0.020, 0.020, 1280),
    "sdcm.fetch": (24, 0.010, 0.010, 1280),
    "workload.trace": (4, 4.0, 4.0, 4_000_000),
    "reuse.distance": (28, 30.0, 20.0, 10_000_000),
    "reuse.mimic": (12, 1.5, 1.5, 3_000_000),
    "reuse.interleave": (12, 2.5, 2.5, 5_000_000),
    "reuse.histogram": (28, 5.0, 5.0, 10_000_000),
}

EXPECTED = {
    "stage_us_per_config.sweep": (0.010 + 0.020 + 0.030) * 1e6 / 1280,
    "dispatch_us_per_config.sweep": 0.020 * 1e6 / 1280,
    "fetch_us_per_config.sweep": 0.010 * 1e6 / 1280,
    "dispatches_per_call.sweep": 12.0,
    "tracegen_us_per_ref.profile": 1.0,
    "rd_us_per_ref.profile": 2.0,
    "mimic_us_per_ref.profile": 0.5,
    "interleave_us_per_ref.profile": 0.5,
    "histogram_us_per_ref.profile": 0.5,
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
def test_reader_on_a_synthetic_snapshot(name, monkeypatch):
    from repro import telemetry

    reader = _reader(name)
    monkeypatch.setattr(telemetry, "snapshot", lambda: _snapshot(SPANS))
    traced = harness.RunContext(seed=1, seconds=1.0, trace=True)
    assert reader.read(traced) == pytest.approx(EXPECTED[name])
    untraced = harness.RunContext(seed=1, seconds=1.0, trace=False)
    assert reader.read(untraced) is None
    # a span the run never recorded reads nothing
    monkeypatch.setattr(telemetry, "snapshot", lambda: {})
    assert reader.read(traced) is None
    # nor does a program without the recorder
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert reader.read(traced) is None


@pytest.mark.parametrize("name", ["sweep.exhaustive", "profile.cold"])
def test_traced_smoke_run_reads_every_span_metric(name, smoke):
    cell = smoke(name, workloads=2)
    out = harness.run_cell(cell, 2**31 + 11, 0.5, True,
                           started=time.perf_counter(),
                           log=lambda *_a: None)
    assert out["correct"] is True
    wanted = {m["name"] for m in cell.per_layer} & set(EXPECTED)
    assert len(wanted) == (4 if name == "sweep.exhaustive" else 5)
    for metric in wanted:
        value = out["metrics"][metric]["value"]
        assert value > 0, metric
