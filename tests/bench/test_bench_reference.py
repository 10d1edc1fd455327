"""The benchmark's plain references agree with the program's own oracles
at the smoke size."""
from __future__ import annotations

import numpy as np
import pytest

from bench.reference import reuse, runtime, sdcm

from bench_cells import ROOT


def _smoke_trace(name="polybench/atx"):
    from repro.workloads import registry

    return registry.resolve(name, "smoke").trace()


@pytest.mark.parametrize("n,keys", [(1, 1), (2, 1), (7, 3), (300, 40),
                                    (1000, 1000), (1025, 17)])
def test_stack_distances_match_the_lru_stack_oracle(n, keys):
    from repro.core.reuse.distance import reuse_distances_ref

    a = np.random.default_rng(n).integers(0, keys, n)
    assert np.array_equal(reuse.stack_distances(a), reuse_distances_ref(a))


def test_stack_distances_match_the_program_at_line_granularity():
    from repro.core.reuse.distance import reuse_distances

    t = _smoke_trace()
    got = reuse.stack_distances(t.addresses, 64)
    assert np.array_equal(got, reuse_distances(t.addresses, 64))


@pytest.mark.parametrize("cores", [2, 3, 4, 8])
def test_mimicry_and_round_robin_match_algorithms_1_and_2(cores):
    from repro.core.trace.interleave import interleave_traces
    from repro.core.trace.mimic import gen_private_traces

    t = _smoke_trace("polybench/cov")
    want = gen_private_traces(t, cores)
    got = reuse.private_traces(t.addresses, t.bb_ids, t.inst_ids,
                               t.shared_mask, cores)
    assert len(got) == cores
    for g, w in zip(got, want):
        assert np.array_equal(g, w.addresses)
    assert np.array_equal(reuse.round_robin(got),
                          interleave_traces(want).addresses)


@pytest.mark.parametrize("cores", [1, 2, 4, 8])
def test_cell_profiles_match_session_artifacts(cores):
    from repro.api import Session
    from repro.workloads import registry

    from bench.common import trace_arrays

    w = registry.resolve("polybench/lu", "smoke")
    art = Session().artifacts(w, cores)
    got = reuse.cell_profiles(trace_arrays(w.trace()), cores, 64)
    for which, prof in (("prd", art.prd), ("crd", art.crd)):
        assert np.array_equal(got[which][0], prof.distances)
        assert np.array_equal(got[which][1], prof.counts)


@pytest.mark.parametrize("assoc,blocks", [(1, 64), (8, 512), (20, 327680),
                                          (16, 16), (4, 4096)])
def test_sdcm_matches_the_float64_oracle(assoc, blocks):
    from repro.core import sdcm as oracle
    from repro.core.reuse.profile import profile_from_distances

    d = np.concatenate([[-1, -1, 0, 3], np.random.default_rng(assoc)
                        .integers(0, 3 * blocks, 500)])
    prof = profile_from_distances(d)
    got = sdcm.hit_rate(prof.distances, prof.counts, assoc, blocks)
    assert got == pytest.approx(oracle.hit_rate(prof, assoc, blocks),
                                abs=1e-10)


def test_eq_runtime_matches_the_program():
    from repro.core.runtime_model import predict_runtime_s
    from repro.hw.targets import CPU_TARGETS
    from repro.workloads import registry

    from bench.common import OP_CLASSES, machine_of

    w = registry.resolve("polybench/atx", "smoke")
    counts = {k: float(getattr(w.op_counts, k)) for k in OP_CLASSES}
    for target in CPU_TARGETS.values():
        rates = [0.9, 0.95, 0.99]
        for cores in (1, 2, 4, 8):
            want = predict_runtime_s(target, rates, w.op_counts, cores)
            got = runtime.eq_runtime_s(machine_of(target), rates, counts,
                                       cores)
            assert got == pytest.approx(want["t_pred_s"], rel=1e-12)


def test_ecm_runtime_matches_the_program():
    from repro.core.incore import ECMRuntimeModel
    from repro.hw.targets import CPU_TARGETS
    from repro.workloads import registry

    from bench.common import OP_CLASSES, machine_of

    w = registry.resolve("polybench/atx", "smoke")
    counts = {k: float(getattr(w.op_counts, k)) for k in OP_CLASSES}
    for target in CPU_TARGETS.values():
        m = machine_of(target)
        rates = np.array([[0.9, 0.95, 0.99], [0.5, 0.6, 0.6]])
        cores = np.array([1.0, 4.0])
        beta = np.array([m["level_beta_cy"][1:] + [m["ram_beta_cy"]]] * 2)
        got = runtime.ecm_runtime_s(m, rates, counts, cores, beta)
        for i in range(2):
            names = [lv.name for lv in target.levels]
            want = ECMRuntimeModel().runtime(
                target, dict(zip(names, rates[i])), w.op_counts,
                int(cores[i]))
            assert got[i] == pytest.approx(want["t_pred_s"], rel=1e-12)


def test_configuration_machines_are_the_programs_table5_targets():
    import json

    from repro.hw.targets import resolve_target

    from bench.common import machine_of

    for path in (ROOT / "bench" / "configs").glob("*.json"):
        for m in json.loads(path.read_text())["machines"]:
            assert machine_of(resolve_target(m["name"])) == m
