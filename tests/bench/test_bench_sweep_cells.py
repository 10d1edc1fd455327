"""The cells ``sweep.hillclimb`` and ``sweep.canneal`` and the two
metrics that read the ``sdcm.eval`` spans: the readers on synthetic
snapshots and where they find nothing, each new cell run in-process at
the smoke preset on the CPU, and what the canneal configuration
states."""
from __future__ import annotations

import json
import math
import sys
import time

import pytest

from bench import harness

from bench_cells import ROOT

NEW_CELLS = ["sweep.hillclimb", "sweep.canneal"]
SWEEP_MAX_ELEMS = 1 << 23

SPANS = {
    "explore.evaluate": (2, 0.100, 0.010, 1280),
    "sdcm.dispatch": (40, 0.020, 0.010, 1280),
    "sdcm.eval": (40, 0.004, 0.004, 40 * 16 * 262144),
}
TRACE = {"busy_s": 0.5, "window_s": 1.0, "breakdown": {}}

EXPECTED = {
    "elems_per_dispatch.sweep": 16 * 262144,
    "device_ns_per_elem.sweep": 0.5 * 1e9 / (40 * 16 * 262144),
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


def _traced():
    ctx = harness.RunContext(seed=1, seconds=1.0, trace=True)
    ctx.device_trace = TRACE
    return ctx


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_eval_reader_on_a_synthetic_snapshot(name, monkeypatch):
    from repro import telemetry

    reader = _reader(name)
    monkeypatch.setattr(telemetry, "snapshot", lambda: _snapshot(SPANS))
    assert reader.read(_traced()) == pytest.approx(EXPECTED[name])
    assert reader.read(harness.RunContext(seed=1, seconds=1.0,
                                          trace=False)) is None
    monkeypatch.setattr(telemetry, "snapshot", lambda: {})
    assert reader.read(_traced()) is None
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert reader.read(_traced()) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_eval_readers_read_nothing_without_the_eval_span(name, monkeypatch):
    """A program that records no ``sdcm.eval`` (the parent of this
    metric) reads nothing, and the device metric nothing without a
    device trace."""
    from repro import telemetry

    spans = {k: v for k, v in SPANS.items() if k != "sdcm.eval"}
    monkeypatch.setattr(telemetry, "snapshot", lambda: _snapshot(spans))
    assert _reader(name).read(_traced()) is None
    if name.startswith("device_"):
        monkeypatch.setattr(telemetry, "snapshot",
                            lambda: _snapshot(SPANS))
        ctx = _traced()
        ctx.device_trace = None
        assert _reader(name).read(ctx) is None


def _run(cell, trace=False, seed=2**31 + 5, seconds=0.5, log=None):
    return harness.run_cell(cell, seed, seconds, trace,
                            started=time.perf_counter(),
                            log=log or (lambda *_a: None))


@pytest.mark.parametrize("name", NEW_CELLS)
def test_new_cell_result_has_the_contract_shape(name, smoke):
    cell = smoke(name, workloads=1)
    out = _run(cell)
    json.dumps(out)
    assert out["correct"] is True, out["checks"]
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in out["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] > 0
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]


@pytest.mark.parametrize("name", NEW_CELLS)
def test_new_cell_traced_run_reads_its_host_metrics(name, smoke):
    cell = smoke(name, workloads=1)
    out = _run(cell, trace=True)
    assert out["correct"] is True
    per_layer = {m["name"] for m in cell.per_layer}
    assert "elems_per_dispatch.sweep" in per_layer
    assert ("device_ns_per_elem.sweep" in per_layer) == (
        name == "sweep.canneal")
    host = {m for m in per_layer if not m.startswith("device_")}
    assert host <= set(out["metrics"]) <= per_layer
    assert 0 < out["metrics"]["elems_per_dispatch.sweep"]["value"] \
        <= SWEEP_MAX_ELEMS


def test_hillclimb_window_compiles_nothing_and_rounds_stay_small(smoke):
    """Set-up compiles every shape a round can dispatch; the window's
    rounds score the current point and its neighbours only."""
    from bench.drivers import agent

    cell = smoke("sweep.hillclimb", workloads=1)
    lines: list[str] = []
    out = _run(cell, seconds=1.0, log=lines.append)
    assert out["correct"] is True
    assert "[window] compiles in window: 0 XLA, 0 kernel signatures" \
        in lines
    from repro.explore import SearchSpace

    most = agent.round_size(SearchSpace(**cell.config["space"]))
    assert most == 1 + 2 * 3          # sets, ways and cores vary
    window = next(x for x in lines if " rounds of " in x)
    configs, rounds = int(window.split()[1]), int(window.split()[4])
    assert 0 < configs <= most * rounds


def test_hillclimb_window_ends_with_the_call_that_crosses(smoke,
                                                          monkeypatch):
    """A zero-second window scores one round and stops its climb."""
    from bench.drivers import agent

    cell = smoke("sweep.hillclimb", workloads=1)
    monkeypatch.setattr(agent, "warm", lambda *_a: None)
    ctx = harness.RunContext(seed=2**31 + 7, seconds=0.0, trace=False)
    state = agent.setup(cell.config, cell.traffic, ctx)
    agent.window(state, ctx)
    assert len(state["answers"]) == 1
    assert ctx.records["configs"] == len(state["answers"][0][0])
    assert ctx.records["configs"] <= agent.round_size(state["space"])


def test_canneal_configuration_states_the_published_netlist():
    """The cell's configuration runs the published 400,000-element
    netlist, cut in temperature steps only, wide enough that the 8-core
    shared profile has at least 100,000 distinct distances."""
    from repro.workloads import parsec

    cfg = json.loads((ROOT / "bench" / "configs"
                      / "cnl-simlarge-t4.e5v4-l3.json").read_text())
    (entry,) = cfg["workloads"]
    kwargs = parsec.SIZE_PRESETS[entry["preset"]]
    assert kwargs["num_elements"] == 400_000
    assert kwargs["swaps_per_temp"] == 15_000
    assert kwargs["temp_steps"] == cfg["temperature_steps"]["run"] == 4
    assert cfg["reduced"] == ["temperature_steps"]
    assert entry["profile_lengths"]["8"][1] >= 100_000
    atx = json.loads((ROOT / "bench" / "configs"
                      / "atx-n1000.e5v4-l3.json").read_text())
    for key in ("machines", "space", "line_size", "strategy", "precision",
                "limits"):
        assert cfg[key] == atx[key], key
