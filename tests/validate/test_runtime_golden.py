"""Golden runtime-error aggregates: pins every named stage-4 model's
accuracy on a small seeded matrix, so a model or parameter edit shows
its accuracy delta in the diff instead of drifting silently.

The pipeline is deterministic (seeded mimicry, float64 throughout), so
the committed values hold to ~1e-6; a legitimate model change updates
them HERE, alongside the change that moved them.
"""
from __future__ import annotations

import pytest

from repro.validate.runner import MatrixSpec, run_validation

GOLDEN_SPEC = MatrixSpec(
    workloads=("polybench/atx", "polybench/mvt", "polybench/jcb"),
    core_counts=(1, 4),
    strategies=("round_robin",),
    sizes="smoke",
    binned_check=False,
)

# Committed aggregates for GOLDEN_SPEC (relative/absolute error in %).
GOLDEN_HIT_ERR_PCT = 0.259646889555145
GOLDEN_RUNTIME_ERR_PCT = 1.367613486290153
GOLDEN_MODEL_ERR_PCT = {
    "eq": 1.367613486290153,
    "ecm": 71.663113522307130,
    "roofline": 90.851810925179830,
}
GOLDEN_CELLS = 18
TOL = 1e-6


@pytest.fixture(scope="module")
def summary():
    return run_validation(GOLDEN_SPEC, artifact_dir=None)


def test_golden_hit_and_runtime_aggregates(summary):
    agg = summary["aggregates"]["overall"]
    assert agg["cells"] == GOLDEN_CELLS
    assert agg["hit_rate_err_pct"]["ours"] == pytest.approx(
        GOLDEN_HIT_ERR_PCT, abs=TOL)
    assert agg["runtime_err_pct"]["ours"] == pytest.approx(
        GOLDEN_RUNTIME_ERR_PCT, abs=TOL)


def test_golden_per_model_aggregates(summary):
    models = summary["aggregates"]["runtime_models"]
    assert set(models) == set(GOLDEN_MODEL_ERR_PCT)
    for name, expected in GOLDEN_MODEL_ERR_PCT.items():
        assert models[name]["overall_rel_err_pct"] == pytest.approx(
            expected, abs=TOL), name
        assert models[name]["cells"] == GOLDEN_CELLS


def test_eq_model_matches_legacy_runtime_metric(summary):
    """The per-model scoring of `eq` and the legacy per-cell
    runtime_rel_err_pct are the same number by construction — both are
    the default CPU chain against the exact-rates reference."""
    agg = summary["aggregates"]
    assert agg["runtime_models"]["eq"]["overall_rel_err_pct"] == \
        pytest.approx(agg["overall"]["runtime_err_pct"]["ours"], abs=1e-12)


def test_runtime_gate_holds_on_golden_matrix(summary):
    """The CI gate's criterion on this matrix: the instruction-aware
    ECM model must beat (or tie) the crude roofline baseline."""
    from repro.validate.__main__ import check_runtime_gate

    passed, msg = check_runtime_gate(summary["aggregates"])
    assert passed, msg
