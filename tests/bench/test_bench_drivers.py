"""Each traffic driver, run in-process at the smoke preset on the CPU,
gives a result of the contract's shape with ``correct`` true."""
from __future__ import annotations

import json
import math
import time

import pytest

from bench import harness

CELLS = ["sweep.exhaustive", "profile.cold"]


def _run(cell, trace=False, seed=2**31 + 5):
    return harness.run_cell(cell, seed, 0.5, trace,
                            started=time.perf_counter(),
                            log=lambda *_a: None)


@pytest.mark.parametrize("name", CELLS)
def test_driver_result_has_the_contract_shape(name, smoke):
    cell = smoke(name, workloads=2)
    out = _run(cell)
    json.dumps(out)
    assert out["correct"] is True, out["checks"]
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    for name_, m in out["metrics"].items():
        assert m["unit"] == units[name_]
        assert math.isfinite(m["value"]) and m["value"] > 0
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"]


@pytest.mark.parametrize("name", ["sweep.exhaustive", "profile.cold"])
def test_traced_run_reports_per_layer_metrics_it_can_read(name, smoke):
    """On the CPU no device plane exists: the device metrics are left
    out, the host-span metrics are read."""
    cell = smoke(name, workloads=2)
    out = _run(cell, trace=True)
    assert out["correct"] is True
    assert "busy_s" not in out["device"]
    per_layer = {m["name"] for m in cell.per_layer}
    assert set(out["metrics"]) <= per_layer
    host = {m for m in per_layer if not m.startswith("device_")}
    assert host <= set(out["metrics"])


def test_profile_set_up_compiles_every_grid_shape_the_builds_use(
        smoke, monkeypatch):
    """Set-up runs the grid on profiles of the stated lengths alone;
    the window's real builds then ask for no row shape it missed."""
    import repro.api.batched as batched
    from bench.common import resolve_source
    from bench.drivers import profile

    seen: list[tuple] = []
    real = batched._record_signature
    monkeypatch.setattr(batched, "_record_signature",
                        lambda sig: seen.append(sig) or real(sig))
    cell = smoke("profile.cold", workloads=3)
    sources = {w["name"]: resolve_source(w)
               for w in cell.config["workloads"]}
    profile.warm_grid(cell.config, sources)
    warmed = set(seen)
    seen.clear()
    for source in sources.values():
        profile.build(cell.config, source)
    assert seen and set(seen) <= warmed


@pytest.mark.parametrize("seconds,passes", [(0.0, 1), (1e9, 3)])
def test_profile_window_runs_whole_passes_of_the_mix(seconds, passes,
                                                     smoke, monkeypatch):
    """The window builds the mix's workloads in whole passes, each in
    the seed's order, and ends with the pass that crosses
    ``--seconds``: every seed builds the same set."""
    from bench.drivers import profile

    cell = smoke("profile.cold", workloads=4)
    names = [w["name"] for w in cell.config["workloads"]][1:]
    traffic = dict(cell.traffic, workloads=names)
    ctx = harness.RunContext(seed=2**31 + 11, seconds=seconds, trace=False)
    done: list[str] = []

    def build(_config, source, _ctx=None):
        done.append(source.workload_name)
        if len(done) == 7:
            ctx.seconds = 0.0
        return {"refs": 1, "profiles": {}, "cells": {}}

    monkeypatch.setattr(profile, "build", build)
    monkeypatch.setattr(profile, "warm_grid", lambda *_a: None)
    state = profile.setup(cell.config, traffic, ctx)
    profile.window(state, ctx)
    assert len(done) == 3 * passes == ctx.records["attempted"]
    for k in range(passes):
        assert sorted(done[3 * k:3 * k + 3]) == sorted(names)
    assert profile.arriving(cell.config, traffic) == names
