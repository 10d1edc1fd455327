"""``BENCHMARK.json`` and the files it names: every one is found by name,
keeps to the benchmark's contract, and a new cell or metric is a new
file that edits none already there."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness

from bench_cells import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")


def test_benchmark_has_exactly_the_contract_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_configurations_are_files_of_their_own():
    used = {w["config"] for w in SPEC["workloads"]}
    files = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["why"])
        assert c["name"] in used and c["file"] not in files
        files.add(c["file"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"]
        assert set(c["reduced"]) <= set(body) and len(c["reduced"]) <= 16
        for key in ("machines", "workloads", "limits", "assumed"):
            assert key in body


def test_cells_name_their_files_and_report_the_contracts_metrics():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    configs = {c["name"] for c in SPEC["configs"]}
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and LINE.match(w["why"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell = harness.load_cell(w["name"])
        driver = cell.driver()
        for fn in ("setup", "window", "check", "control"):
            assert callable(getattr(driver, fn))
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader_found_by_name(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    reader = harness.load_module(ROOT / "bench" / "metrics"
                                 / f"{metric['name']}.py")
    ctx = harness.RunContext(seed=1, seconds=1.0, trace=False)
    assert reader.read(ctx) is None or metric["name"] == "setup_s"
    if metric in SPEC["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert LINE.match(metric["layer"])


def test_every_traffic_and_metric_file_loads_by_name():
    """Every reader is a metric of ``BENCHMARK.json`` and every mix is
    a cell's, each with its driver."""
    named = {m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]}
    readers = {p.name[:-3] for p in (ROOT / "bench" / "metrics").glob("*.py")
               if p.name != "__init__.py"}
    assert readers == named
    for name in readers:
        reader = harness.load_module(ROOT / "bench" / "metrics"
                                     / f"{name}.py")
        assert callable(reader.read)
    mixes = {p.stem for p in (ROOT / "bench" / "traffic").glob("*.json")}
    assert mixes == {w["traffic"] for w in SPEC["workloads"]}
    for path in (ROOT / "bench" / "traffic").glob("*.json"):
        traffic = json.loads(path.read_text())
        driver = harness.load_module(ROOT / "bench" / "drivers"
                                     / f"{traffic['driver']}.py")
        for fn in ("setup", "window", "check", "control"):
            assert callable(getattr(driver, fn))


def test_a_new_cell_and_metric_are_new_files_only(tmp_path, smoke):
    """Copy the benchmark, add a configuration, a traffic mix, a cell and
    a metric as new files, run the new cell: nothing already there is
    edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    base = smoke("sweep.exhaustive", workloads=1).config
    (tmp_path / "bench" / "configs" / "tiny.json").write_text(json.dumps(
        dict(base, name="tiny")))
    (tmp_path / "bench" / "traffic" / "tiny-sweep.json").write_text(
        json.dumps({"driver": "sweep", "inner": "vmap"}))
    (tmp_path / "bench" / "metrics" / "sweep_calls.py").write_text(
        "def read(ctx):\n"
        "    n = sum(1 for s in ctx.spans if s.name == 'sweep')\n"
        "    return n or None\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                            "file": "bench/configs/tiny.json", "why": "t"})
    spec["workloads"].append({"name": "sweep.tiny", "config": "tiny",
                              "traffic": "tiny-sweep", "chips": 1,
                              "why": "t"})
    spec["end_to_end"].append({"name": "sweep_calls", "unit": "calls",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["sweep.tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell("sweep.tiny", root=tmp_path)
    out = harness.run_cell(cell, 3, 0.3, False, started=time.perf_counter(),
                           log=lambda *_a: None)
    assert out["correct"] is True
    assert out["metrics"]["sweep_calls"]["value"] >= 1
    assert set(out["metrics"]) == {"sweep_calls", "setup_s"}
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_cli_refuses_a_device_that_is_not_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "sweep.exhaustive", "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_fail():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.NoAccelerator):
        harness.peaks("TPU v9 imaginary")
