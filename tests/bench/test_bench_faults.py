"""The comparison that decides ``correct`` fails where it must: the
control (the reference in bfloat16, in the program's place) fails a
limit, and a run whose timed path is broken underneath reads false."""
from __future__ import annotations

import time

import numpy as np
import pytest

from bench import harness


def _run(cell, control=False):
    return harness.run_cell(cell, 7, 0.5, False, started=time.perf_counter(),
                            log=lambda *_a: None, control=control)


@pytest.mark.parametrize("name", ["sweep.exhaustive", "profile.cold"])
def test_control_fails_a_limit_the_program_meets(name, smoke):
    out = _run(smoke(name, workloads=2), control=True)
    assert out["correct"] is True
    failed = [k for k, c in out["control"].items() if c["value"] > c["limit"]]
    assert "hit_rate_gap" in failed
    ratio = out["control"]["hit_rate_gap"]["value"] / \
        out["checks"]["hit_rate_gap"]["value"]
    assert ratio > 100


# an altered answer moves one hit rate by ten times the widest
# ``hit_rate_gap`` limit in bench/configs
ALTERED = 1e-2


def _perturb_sweep(monkeypatch, how):
    from repro.explore.engine import EvalResult, FusedSweepEvaluator

    real = FusedSweepEvaluator.evaluate

    def evaluate(self, configs):
        res = real(self, configs)
        rates, t = res.rates.copy(), res.t_pred_s.copy()
        if how == "altered":
            rates[len(rates) // 2, -1] += ALTERED
        else:  # half of the batch left out
            rates[len(rates) // 2:] = 0.0
            t[len(t) // 2:] = 0.0
        return EvalResult(scores=res.scores, rates=rates, t_pred_s=t)

    monkeypatch.setattr(FusedSweepEvaluator, "evaluate", evaluate)


def _perturb_grid(monkeypatch, how):
    import repro.api.batched as batched

    real = batched.batched_hit_rates

    def batched_hit_rates(items):
        out = real(items)
        if how == "altered":
            k = next(iter(out[-1]))
            out[-1] = dict(out[-1], **{k: out[-1][k] + ALTERED})
        else:
            for i in range(len(out) // 2, len(out)):
                out[i] = {k: 0.0 for k in out[i]}
        return out

    monkeypatch.setattr(batched, "batched_hit_rates", batched_hit_rates)


def _perturb_profile(monkeypatch):
    import repro.api.stages as stages
    from repro.core.reuse.profile import ReuseProfile

    real = stages.profile_from_distances

    def profile_from_distances(rds):
        p = real(rds)
        counts = p.counts.copy()
        counts[-1] += 1
        return ReuseProfile(p.distances, counts, p.total + 1)

    monkeypatch.setattr(stages, "profile_from_distances",
                        profile_from_distances)


FAULTS = {
    ("sweep.exhaustive", "answer altered"): lambda mp: _perturb_sweep(
        mp, "altered"),
    ("sweep.exhaustive", "half the batch left out"): lambda mp:
        _perturb_sweep(mp, "half"),
    ("profile.cold", "answer altered"): lambda mp: _perturb_grid(
        mp, "altered"),
    ("profile.cold", "half the batch left out"): lambda mp: _perturb_grid(
        mp, "half"),
    ("profile.cold", "profile altered"): _perturb_profile,
}


@pytest.mark.parametrize("name,fault", sorted(FAULTS))
def test_broken_timed_path_reads_not_correct(name, fault, smoke,
                                             monkeypatch):
    limits = smoke(name, workloads=1).config["limits"]
    assert ALTERED >= 10 * limits["hit_rate_gap"]
    cell = smoke(name, workloads=2)
    FAULTS[(name, fault)](monkeypatch)
    out = _run(cell)
    assert out["correct"] is False
    assert any(not (c["value"] <= c["limit"])
               for c in out["checks"].values())


def test_unanswered_calls_and_missing_values_fail(smoke):
    from bench.drivers import sweep

    cell = smoke("sweep.exhaustive", workloads=1)
    state = {"config": cell.config}
    ref = np.zeros((4, 3)), np.ones(4)
    checks = sweep.compare(state, [(np.arange(4), None, None),
                                   (np.arange(4), np.full((4, 3), np.nan),
                                    np.ones(4))], *ref)
    assert not all(c.ok for c in checks)
