"""The per-layer metric that counts SDCM result reads per sweep call
(``sdcm.fetch`` spans over ``explore.evaluate`` spans): the reader on
synthetic snapshots of a program that reads each dispatch in turn and of
one that reads them all at once, where it finds nothing, and read from
traced smoke runs on the CPU."""
from __future__ import annotations

import sys
import time

import pytest

from bench import harness

from bench_cells import ROOT

NAME = "fetches_per_call.sweep"


def _spans(fetches):
    return {
        "explore.evaluate": (2, 0.100, 0.010, 1280),
        "sdcm.dispatch": (24, 0.020, 0.020, 1280),
        "sdcm.fetch": (fetches, 0.010, 0.010, 1280),
    }


@pytest.fixture(autouse=True)
def clean_recorder():
    from repro import telemetry

    telemetry.reset()
    yield
    telemetry.reset()


def _reader():
    return harness.load_module(ROOT / "bench" / "metrics" / f"{NAME}.py")


def _snapshot(spans):
    return {k: {"count": c, "total_s": t, "self_s": s, "n": n}
            for k, (c, t, s, n) in spans.items()}


def _ctx(trace):
    return harness.RunContext(seed=1, seconds=1.0, trace=trace)


@pytest.mark.parametrize("fetches,want", [(24, 12.0), (2, 1.0)],
                         ids=["read_per_dispatch", "read_per_call"])
def test_fetch_reader_on_a_synthetic_snapshot(fetches, want, monkeypatch):
    from repro import telemetry

    reader = _reader()
    monkeypatch.setattr(telemetry, "snapshot",
                        lambda: _snapshot(_spans(fetches)))
    assert reader.read(_ctx(True)) == pytest.approx(want)
    assert reader.read(_ctx(False)) is None
    monkeypatch.setattr(telemetry, "snapshot", lambda: {})
    assert reader.read(_ctx(True)) is None
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert reader.read(_ctx(True)) is None


@pytest.mark.parametrize("missing", ["sdcm.fetch", "explore.evaluate"])
def test_fetch_reader_reads_nothing_without_its_spans(missing, monkeypatch):
    from repro import telemetry

    spans = {k: v for k, v in _spans(2).items() if k != missing}
    monkeypatch.setattr(telemetry, "snapshot", lambda: _snapshot(spans))
    assert _reader().read(_ctx(True)) is None


@pytest.mark.parametrize("name", ["sweep.exhaustive", "sweep.hillclimb"])
def test_traced_smoke_sweep_reads_one_fetch_per_call(name, smoke):
    cell = smoke(name, workloads=1)
    assert NAME in {m["name"] for m in cell.per_layer}
    out = harness.run_cell(cell, 2**31 + 13, 0.5, True,
                           started=time.perf_counter(),
                           log=lambda *_a: None)
    assert out["correct"] is True
    assert out["metrics"][NAME]["value"] == 1.0
    assert out["metrics"]["dispatches_per_call.sweep"]["value"] >= 1.0
