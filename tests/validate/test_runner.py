"""Validation runner + report (ISSUE-3 tentpole acceptance): the
matrix runs through the Session grid, a second run against the same
artifact dir performs zero reuse-profile recomputations, and the
report renders the paper comparison from the merged summary."""
from __future__ import annotations

import json

import pytest

from repro.validate import (
    MatrixSpec,
    generate_report,
    run_validation,
    save_results,
)
from repro.validate.reference import (
    PAPER_ARCH_CLAIMS,
    PAPER_OVERALL,
    PAPER_TABLE4,
)
from repro.workloads.polybench import MAKERS, SIZE_PRESETS

TINY = MatrixSpec(
    workloads=("atx", "jcb"),
    core_counts=(1, 2),
    strategies=("round_robin",),
    sizes="smoke",
)


def test_reference_tables_cover_roster():
    """Every MAKERS workload decodes to a Table-4 row, and the per-arch
    claims average to the paper's headline aggregates."""
    assert set(PAPER_TABLE4) == set(MAKERS)
    archs = list(PAPER_ARCH_CLAIMS.values())
    assert sum(c.hit_rate_err_pct for c in archs) / len(archs) == \
        pytest.approx(PAPER_OVERALL.hit_rate_err_pct, abs=0.01)
    assert sum(c.runtime_err_pct for c in archs) / len(archs) == \
        pytest.approx(PAPER_OVERALL.runtime_err_pct, abs=0.01)
    for preset in SIZE_PRESETS.values():
        assert set(preset) <= set(MAKERS)


def test_matrix_id_stable_and_spec_sensitive():
    assert TINY.matrix_id() == TINY.matrix_id()
    other = MatrixSpec(workloads=("atx",), sizes="smoke")
    assert other.matrix_id() != TINY.matrix_id()


def test_runner_scores_every_cell(tmp_path):
    summary = run_validation(TINY, artifact_dir=tmp_path)
    cells = (len(TINY.workloads) * len(TINY.targets)
             * len(TINY.core_counts) * len(TINY.strategies))
    assert len(summary["records"]) == cells
    for rec in summary["records"]:
        assert set(rec["levels"]) == {"L1", "L2", "L3"}
        for entry in rec["levels"].values():
            assert 0.0 <= entry["predicted"] <= 1.0
            assert 0.0 <= entry["exact"] <= 1.0
            assert entry["abs_err_pct"] >= 0.0
        assert rec["t_pred_s"] > 0 and rec["t_exact_rates_s"] > 0
    agg = summary["aggregates"]["overall"]
    assert agg["cells"] == cells
    assert agg["hit_rate_err_pct"]["paper"] == PAPER_OVERALL.hit_rate_err_pct
    assert set(summary["aggregates"]["per_arch"]) == set(TINY.targets)
    assert summary["reference"]["overall"]["runtime_err_pct"] == 9.08


def test_binned_profile_deviation_within_tolerance(tmp_path):
    """ISSUE-5 acceptance: SDCM hit rates from fused device-binned
    profiles stay within 1e-3 absolute of the exact-profile rates on
    every scored level cell, and the runner records the comparison."""
    summary = run_validation(TINY, artifact_dir=tmp_path)
    bp = summary["aggregates"]["binned_profile"]
    assert bp["cells"] > 0
    assert bp["max_abs_dev"] <= bp["tolerance"] == 1e-3
    assert bp["within_tolerance"]
    for rec in summary["records"]:
        assert set(rec["binned_abs_dev"]) == set(rec["levels"])


def test_binned_check_can_be_disabled(tmp_path):
    spec = MatrixSpec(workloads=("atx",), core_counts=(1,),
                      strategies=("round_robin",), sizes="smoke",
                      binned_check=False)
    summary = run_validation(spec, artifact_dir=tmp_path)
    assert summary["aggregates"]["binned_profile"]["cells"] == 0
    assert all("binned_abs_dev" not in r for r in summary["records"])


def test_sampled_profile_deviation_within_bound(tmp_path):
    """ISSUE-9 acceptance: every sampled SDCM hit rate deviates from
    the exact-profile prediction by less than the error bound its own
    profile declared, and the runner records both per level cell."""
    summary = run_validation(TINY, artifact_dir=tmp_path)
    sp = summary["aggregates"]["sampled_profile"]
    assert sp["cells"] > 0
    assert sp["rate"] == TINY.sampled_rate == 0.5
    assert sp["max_declared_bound"] > 0.0
    assert sp["bound_exceedances"] == 0 and sp["within_bound"]
    for rec in summary["records"]:
        assert set(rec["sampled_abs_dev"]) == set(rec["levels"])
        assert set(rec["sampled_bound"]) == set(rec["levels"])
        for lvl, dev in rec["sampled_abs_dev"].items():
            assert dev < rec["sampled_bound"][lvl], (rec["workload"], lvl)


def test_sampled_check_can_be_disabled(tmp_path):
    spec = MatrixSpec(workloads=("atx",), core_counts=(1,),
                      strategies=("round_robin",), sizes="smoke",
                      sampled_check=False)
    summary = run_validation(spec, artifact_dir=tmp_path)
    sp = summary["aggregates"]["sampled_profile"]
    assert sp["cells"] == 0 and sp["rate"] is None
    assert all("sampled_abs_dev" not in r for r in summary["records"])


def test_sampling_gate_checker():
    """check_sampling_gate: passes within bound, fails on exceedance,
    and fails LOUDLY (not vacuously) when no sampled cells scored."""
    from repro.validate.__main__ import check_sampling_gate

    good = {"sampled_profile": {
        "cells": 12, "rate": 0.5, "max_abs_dev": 1e-3,
        "max_declared_bound": 5e-2, "bound_exceedances": 0,
        "within_bound": True,
    }}
    ok, msg = check_sampling_gate(good)
    assert ok and msg.startswith("OK")

    bad = {"sampled_profile": {
        "cells": 12, "rate": 0.5, "max_abs_dev": 9e-2,
        "max_declared_bound": 5e-2, "bound_exceedances": 3,
        "within_bound": False,
    }}
    ok, msg = check_sampling_gate(bad)
    assert not ok and "3 cell(s)" in msg

    ok, msg = check_sampling_gate({})
    assert not ok and "no sampled cells" in msg
    ok, msg = check_sampling_gate({"sampled_profile": {"cells": 0}})
    assert not ok


def test_second_run_zero_profile_recomputation(tmp_path):
    """THE acceptance criterion: same artifact_dir, run twice — the
    second run rebuilds no reuse profile and resimulates no baseline."""
    first = run_validation(TINY, artifact_dir=tmp_path)
    assert first["session_stats"]["profile_builds"] > 0
    second = run_validation(TINY, artifact_dir=tmp_path)
    assert second["session_stats"]["profile_builds"] == 0
    assert second["session_stats"]["rd_builds"] == 0
    assert second["session_stats"]["mimic_builds"] == 0
    assert second["session_stats"]["store_hits"] > 0
    # identical scores both times (disk round-trip is lossless)
    assert second["aggregates"]["overall"] == first["aggregates"]["overall"]


def test_report_generation(tmp_path):
    summary = run_validation(TINY, artifact_dir=tmp_path)
    json_path = save_results(summary, tmp_path / "validation.json")
    md_path = generate_report(json_path, tmp_path / "validation.md")
    md = md_path.read_text()
    assert "GENERATED by repro.validate.report" in md
    assert "Aggregate errors vs the paper" in md
    assert "1.23" in md and "9.08" in md        # paper claims present
    for arch in TINY.targets:
        assert f"### {arch}" in md
    assert "ATAX" in md                          # Table-4 decoding used
    # summary json is loadable and self-contained
    payload = json.loads(json_path.read_text())
    assert payload["reference"]["per_arch"].keys() == PAPER_ARCH_CLAIMS.keys()


def test_no_store_defaults_to_serial():
    """The documented zero-config call works: without an artifact_dir
    the runner falls back to in-process execution instead of raising."""
    spec = MatrixSpec(workloads=("atx",), core_counts=(1,),
                      strategies=("round_robin",), sizes="smoke")
    summary = run_validation(spec)           # no artifact_dir, no procs
    assert len(summary["records"]) == 3      # 3 targets x 1 core


def test_model_workload_cell_through_matrix(tmp_path):
    """ISSUE-7 acceptance: a model/<arch>/<step> workload joins the
    validation grid with a TPU VMEM hit-rate cell, and a second run is
    served entirely from the store (declared fingerprint stable)."""
    spec = MatrixSpec(workloads=("model/llama3_8b/decode",),
                      targets=("tpu-v5e",), core_counts=(1,),
                      strategies=("round_robin",), sizes="smoke",
                      binned_check=False)
    summary = run_validation(spec, artifact_dir=tmp_path)
    assert len(summary["records"]) == 1
    rec = summary["records"][0]
    assert rec["workload"] == "model/llama3_8b/decode"
    assert set(rec["levels"]) == {"VMEM"}
    assert 0.0 <= rec["levels"]["VMEM"]["predicted"] <= 1.0
    assert rec["t_pred_s"] > 0          # roofline runtime on the TPU
    assert summary["per_workload"]["model/llama3_8b/decode"]["refs"] > 0

    second = run_validation(spec, artifact_dir=tmp_path)
    assert second["session_stats"]["trace_builds"] == 0
    assert second["session_stats"]["profile_builds"] == 0
    assert second["aggregates"]["overall"] == summary["aggregates"]["overall"]
